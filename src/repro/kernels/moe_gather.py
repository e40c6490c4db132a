"""Pallas TPU kernel: fused MoE dispatch gather (+ optional int8 quantise).

The a2a expert-parallel dispatch (§Perf cell B) builds its send buffer
with a chain of gather → mask → scatter → quantise jnp ops — ~6-8 HBM
passes over the (slots, d) buffer in the lowered HLO.  This kernel does
it in one pass: for each send slot, read the source token row (dynamic
HBM load), scale to int8 (per-row absmax) and write the wire buffer +
scales.  Empty slots (row id -1) write zeros.

Grid: one program per slot block; token matrix stays in ANY/HBM memory
space and is row-gathered with dynamic loads; the slot's output block
lives in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEF_BS = 128


def _gather_kernel(idx_ref, x_hbm, out_ref, scale_ref, *, bs: int, quant: bool):
    def body(i, _):
        row = idx_ref[i]
        valid = row >= 0
        safe = jnp.maximum(row, 0)
        vals = x_hbm[pl.ds(safe, 1), :][0]
        vals = jnp.where(valid, vals, 0).astype(jnp.float32)
        if quant:
            absmax = jnp.max(jnp.abs(vals))
            scale = jnp.maximum(absmax / 127.0, 1e-12)
            q = jnp.clip(jnp.round(vals / scale), -127, 127)
            out_ref[i, :] = q.astype(out_ref.dtype)
            scale_ref[i] = jnp.where(valid, scale, 0.0)
        else:
            out_ref[i, :] = vals.astype(out_ref.dtype)
            scale_ref[i] = jnp.where(valid, 1.0, 0.0)
        return 0

    jax.lax.fori_loop(0, bs, body, 0)


@functools.partial(jax.jit, static_argnames=("quant", "bs", "interpret"))
def dispatch_gather(
    x: jax.Array, idx: jax.Array, *, quant: bool = True, bs: int = DEF_BS,
    interpret: bool = False,
):
    """x: (t, d) token rows; idx: (S,) source row per send slot (-1 empty).

    Returns (buf (S, d) [int8 if quant else x.dtype], scales (S,) f32).
    S must be a multiple of ``bs`` (ops pads).
    """
    t, d = x.shape
    s = idx.shape[0]
    bs_ = min(bs, s)
    assert s % bs_ == 0, (s, bs_)
    out_dtype = jnp.int8 if quant else x.dtype
    kernel = functools.partial(_gather_kernel, bs=bs_, quant=quant)
    return pl.pallas_call(
        kernel,
        grid=(s // bs_,),
        in_specs=[
            pl.BlockSpec((bs_,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((bs_, d), lambda i: (i, 0)),
            pl.BlockSpec((bs_,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, d), out_dtype),
            jax.ShapeDtypeStruct((s,), jnp.float32),
        ],
        interpret=interpret,
    )(idx, x)
