"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import contour_dist as cd
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import pairwise_dist as pd
from repro.kernels import ref
from repro.kernels import ssd_scan as ssd

RNG = np.random.default_rng(0)


def randn(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


class TestPairwiseDist:
    @pytest.mark.parametrize("n,m,d,bn,bm", [
        (128, 128, 2, 64, 64),
        (256, 128, 8, 64, 128),
        (512, 512, 3, 128, 256),
        (64, 64, 16, 64, 64),
    ])
    def test_dist_sweep(self, n, m, d, bn, bm):
        x, y = randn((n, d)), randn((m, d))
        out = pd.pairwise_dist_sq(x, y, bn=bn, bm=bm, interpret=True)
        np.testing.assert_allclose(out, ref.pairwise_dist_sq(x, y),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = randn((128, 2), dtype)
        out = pd.pairwise_dist_sq(x, x, bn=64, bm=64, interpret=True)
        expect = ref.pairwise_dist_sq(x, x)
        np.testing.assert_allclose(out, expect, rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("eps", [0.1, 0.5, 2.0])
    def test_neighbor_count(self, eps):
        x = randn((256, 2))
        mask = jnp.asarray(RNG.random(256) > 0.3)
        got = pd.neighbor_count(x, mask, eps, bn=64, bm=64, interpret=True)
        np.testing.assert_array_equal(got, ref.neighbor_count(x, mask, eps))

    def test_min_label_sweep(self):
        x = randn((128, 2))
        mask = jnp.ones(128, bool)
        labels = jnp.arange(128, dtype=jnp.int32)
        core = jnp.asarray(RNG.random(128) > 0.5)
        got = pd.min_label_sweep(x, mask, labels, core, 0.4, bn=64, bm=64,
                                 interpret=True)
        d2 = np.asarray(ref.pairwise_dist_sq(x, x))
        ok = (d2 <= 0.16) & np.asarray(core)[None, :]
        want = np.where(ok, np.arange(128)[None, :], 2**30).min(1)
        np.testing.assert_array_equal(got, want)


class TestContourMinD2:
    @pytest.mark.parametrize("m,v,bi,bj", [
        (16, 32, 8, 8),
        (32, 64, 8, 8),
        (8, 16, 4, 8),
        (24, 8, 8, 4),
    ])
    def test_sweep(self, m, v, bi, bj):
        contours = jnp.asarray(RNG.uniform(0, 1, (m, v, 2)), jnp.float32)
        counts = jnp.asarray(RNG.integers(0, v + 1, m), jnp.int32)
        valid = jnp.asarray(RNG.random(m) > 0.25)
        vert_valid = (jnp.arange(v)[None, :] < counts[:, None]) & valid[:, None]
        got = cd.contour_min_d2(
            contours.reshape(m * v, 2), vert_valid.reshape(m * v).astype(jnp.int32),
            v, bi=bi, bj=bj, interpret=True)
        want = ref.contour_min_d2(contours, counts, valid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_ops_dispatch_pads_odd_slot_counts(self):
        m, v = 11, 16
        contours = jnp.asarray(RNG.uniform(0, 1, (m, v, 2)), jnp.float32)
        counts = jnp.asarray(RNG.integers(1, v + 1, m), jnp.int32)
        valid = jnp.ones(m, bool)
        want = ref.contour_min_d2(contours, counts, valid)
        prev, ops.FORCE = ops.FORCE, "interpret"
        try:
            got = ops.contour_min_d2(contours, counts, valid)
        finally:
            ops.FORCE = prev
        assert got.shape == (m, m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_centred_offset_data(self):
        """The kernel must survive a large coordinate offset (difference
        form, DESIGN.md §4 item 6)."""
        m, v = 16, 32
        base = jnp.asarray(RNG.uniform(0, 1, (m, v, 2)), jnp.float32)
        counts = jnp.full((m,), v, jnp.int32)
        valid = jnp.ones(m, bool)
        want = ref.contour_min_d2(base, counts, valid)
        prev, ops.FORCE = ops.FORCE, "interpret"
        try:
            got = ops.contour_min_d2(base + 100.0, counts, valid)
        finally:
            ops.FORCE = prev
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)

    def test_empty_slots_get_big(self):
        m, v = 8, 16
        contours = jnp.zeros((m, v, 2), jnp.float32)
        counts = jnp.zeros((m,), jnp.int32)
        valid = jnp.zeros((m,), bool)
        out = np.asarray(ref.contour_min_d2(contours, counts, valid))
        assert (out >= 1e29).all()


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,hkv,sq,skv,d,bq,bk", [
        (1, 4, 4, 128, 128, 32, 64, 64),     # MHA square
        (2, 8, 2, 128, 256, 64, 64, 128),    # GQA, decode-style kv > q
        (1, 4, 1, 256, 256, 32, 128, 64),    # MQA
        (2, 2, 2, 64, 64, 128, 64, 64),      # large head dim
    ])
    def test_causal_sweep(self, b, h, hkv, sq, skv, d, bq, bk):
        q, k, v = randn((b, h, sq, d)), randn((b, hkv, skv, d)), randn((b, hkv, skv, d))
        got = fa.flash_attention(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
        want = ref.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    def test_non_causal(self):
        q, k, v = randn((1, 2, 128, 32)), randn((1, 2, 128, 32)), randn((1, 2, 128, 32))
        got = fa.flash_attention(q, k, v, causal=False, bq=64, bk=64, interpret=True)
        np.testing.assert_allclose(got, ref.flash_attention(q, k, v, causal=False),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("window", [32, 100])
    def test_windowed(self, window):
        q, k, v = randn((1, 2, 192, 32)), randn((1, 2, 192, 32)), randn((1, 2, 192, 32))
        got = fa.flash_attention(q, k, v, causal=True, window=window,
                                 bq=64, bk=64, interpret=True)
        want = ref.flash_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    def test_bf16(self):
        q = randn((1, 2, 128, 32), jnp.bfloat16)
        k = randn((1, 2, 128, 32), jnp.bfloat16)
        v = randn((1, 2, 128, 32), jnp.bfloat16)
        got = fa.flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
        want = ref.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=0.05, atol=0.05)

    def test_chunked_ref_matches_exact(self):
        q, k, v = randn((2, 4, 300, 32)), randn((2, 2, 520, 32)), randn((2, 2, 520, 32))
        for causal in (True, False):
            got = ref.flash_attention_chunked(q, k, v, causal=causal, bq=128, bk=128)
            want = ref.flash_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_chunked_ref_grad(self):
        q, k, v = randn((1, 2, 256, 16)), randn((1, 2, 256, 16)), randn((1, 2, 256, 16))
        g1 = jax.grad(lambda q: ref.flash_attention(q, k, v).sum())(q)
        g2 = jax.grad(lambda q: ref.flash_attention_chunked(q, k, v, bq=64, bk=64).sum())(q)
        np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-4)


class TestSSDScan:
    @pytest.mark.parametrize("b,l,h,dh,ds,chunk", [
        (1, 64, 2, 16, 8, 16),
        (2, 128, 3, 16, 8, 32),
        (1, 256, 1, 32, 16, 64),
        (2, 96, 4, 8, 4, 32),
    ])
    def test_sweep(self, b, l, h, dh, ds, chunk):
        x = randn((b, l, h, dh))
        a = jnp.asarray(-np.abs(RNG.normal(size=(b, l, h))) * 0.1, jnp.float32)
        bb = randn((b, l, h, ds))
        c = randn((b, l, h, ds))
        got = ssd.ssd_scan(x, a, bb, c, chunk=chunk, interpret=True)
        want = ref.ssd_scan(x, a, bb, c)
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)

    def test_chunked_ref(self):
        x = randn((2, 100, 3, 16))
        a = jnp.asarray(-np.abs(RNG.normal(size=(2, 100, 3))) * 0.1, jnp.float32)
        bb = randn((2, 100, 3, 8))
        c = randn((2, 100, 3, 8))
        got = ref.ssd_scan_chunked(x, a, bb, c, chunk=32)
        want = ref.ssd_scan(x, a, bb, c)
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)

    def test_decay_semantics(self):
        """Strong decay ⇒ output ≈ instantaneous c·b x (no history)."""
        b, l, h, dh, ds = 1, 32, 1, 4, 4
        x = randn((b, l, h, dh))
        a = jnp.full((b, l, h), -50.0)
        bb = randn((b, l, h, ds))
        c = randn((b, l, h, ds))
        y = ref.ssd_scan(x, a, bb, c)
        want = jnp.einsum("blhs,blhs->blh", c, bb)[..., None] * x
        np.testing.assert_allclose(y, want, rtol=1e-3, atol=1e-3)
