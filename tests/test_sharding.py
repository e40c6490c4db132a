"""Sharding-rule unit tests (AbstractMesh — no devices needed)."""
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.parallel import api as par
from repro.parallel import sharding as sr


def mesh2(multi_pod=False):
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def ctx(fsdp=False, multi_pod=False):
    return par.ParallelCtx(mesh=mesh2(multi_pod), fsdp=fsdp)


class TestParamSpecs:
    def test_attention_tp(self):
        c = ctx()
        assert sr.spec_for(("blocks", "l0", "mixer", "wq"), (62, 4096, 4096), c) \
            == P(None, None, "model")
        assert sr.spec_for(("blocks", "l0", "mixer", "wo"), (62, 4096, 4096), c) \
            == P(None, "model", None)

    def test_embed_vocab_sharded(self):
        c = ctx()
        assert sr.spec_for(("embed",), (151936, 4096), c) == P("model", None)

    def test_moe_expert_parallel(self):
        c = ctx()
        spec = sr.spec_for(("blocks", "l0", "ffn", "w1"), (61, 384, 7168, 2048), c)
        assert spec == P(None, "model", None, None)

    def test_dense_ffn_vs_moe_disambiguation(self):
        c = ctx()
        dense = sr.spec_for(("blocks", "l0", "ffn", "w1"), (36, 4096, 12288), c)
        assert dense == P(None, None, "model")

    def test_shared_expert_is_dense_tp(self):
        c = ctx()
        spec = sr.spec_for(("blocks", "l0", "ffn", "shared", "w2"), (61, 2048, 7168), c)
        assert spec == P(None, "model", None)

    def test_non_divisible_replicates(self):
        c = ctx()
        # 40 heads * 64 hd = 2560; 2560 % 16 == 0 so it shards...
        assert sr.spec_for(("blocks", "l0", "mixer", "wq"), (62, 2560, 2560), c) \
            == P(None, None, "model")
        # ...but a 61-dim can't shard over 16
        assert sr.spec_for(("blocks", "l0", "mixer", "wq"), (62, 2560, 61), c) \
            == P(None, None, None)

    def test_fsdp_adds_data_axis(self):
        c = ctx(fsdp=True)
        spec = sr.spec_for(("blocks", "l0", "mixer", "wq"), (62, 4096, 4096), c)
        assert spec == P(None, "data", "model")

    def test_fsdp_multipod_uses_both_dp_axes(self):
        c = ctx(fsdp=True, multi_pod=True)
        spec = sr.spec_for(("blocks", "l0", "mixer", "wq"), (62, 4096, 4096), c)
        assert spec == P(None, ("pod", "data"), "model")

    def test_norms_replicated_tp(self):
        c = ctx()
        spec = sr.spec_for(("blocks", "l0", "norm1", "w"), (62, 4096), c)
        assert spec == P(None, None) or spec == P(None, "data")


class TestCtxHelpers:
    def test_spec_drops_missing_axes(self):
        c = par.ParallelCtx(mesh=mesh2(multi_pod=False))
        assert c.spec("batch", None, "heads") == P(("data",), None, "model")

    def test_axis_size(self):
        c = par.ParallelCtx(mesh=mesh2(multi_pod=True))
        assert c.axis_size("batch") == 32
        assert c.axis_size("experts") == 16

    def test_no_mesh_no_op(self):
        x = jnp.ones((4, 4))
        assert par.constrain(x, "batch", None) is x
