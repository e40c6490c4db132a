"""Training step builder: value_and_grad + optimizer under a mesh.

The returned step is a single jit with explicit in/out shardings (state
donated).  Grad accumulation happens inside the jit via lax.scan over
microbatches; optional int8 error-feedback gradient compression wraps
the cross-DP gradient reduction (parallel/compress.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.parallel import api as par
from repro.parallel import sharding as shard_rules
from repro.train import optimizer as opt_mod


class TrainState(NamedTuple):
    params: Any
    opt: Any
    step: jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: opt_mod.OptConfig = opt_mod.OptConfig()
    microbatches: int = 1
    param_dtype: str = "float32"
    seed: int = 0


def make_train_state(cfg: ModelConfig, tcfg: TrainConfig, key=None) -> TrainState:
    key = key if key is not None else jax.random.PRNGKey(tcfg.seed)
    dtype = jnp.dtype(tcfg.param_dtype)
    params = T.init_params(cfg, key, dtype=dtype)
    opt_state = opt_mod.init_state(tcfg.opt, params)
    return TrainState(params, opt_state, jnp.zeros((), jnp.int32))


def _loss_and_grads(cfg, tcfg, params, batch, grad_shardings=None):
    if tcfg.microbatches <= 1:
        loss, grads = jax.value_and_grad(lambda p: T.loss_fn(cfg, p, batch))(params)
        return loss, grads

    n = tcfg.microbatches

    def constrain_g(tree):
        # Keep accumulated grads in their FSDP-sharded layout: XLA then
        # reduce-scatters each microbatch's gradient instead of
        # all-reducing it (bytes / (2 * dp_lanes) — §Perf iteration C2).
        if grad_shardings is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            grad_shardings)

    micro = jax.tree.map(lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)

    def body(acc, mb):
        loss_acc, g_acc = acc
        loss, g = jax.value_and_grad(lambda p: T.loss_fn(cfg, p, mb))(params)
        g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
        return (loss_acc + loss, constrain_g(g_acc)), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros(()), constrain_g(g0)), micro)
    inv = 1.0 / n
    return loss * inv, jax.tree.map(lambda g: g * inv, grads)


def build_train_step(cfg: ModelConfig, tcfg: TrainConfig, pctx: par.ParallelCtx):
    """Returns (step_fn, state_shardings, batch_sharding_fn).

    step_fn(state, batch) -> (state, metrics); jit-with-shardings happens
    in the caller so a dry-run can .lower() without allocating."""

    grad_shardings = None
    if pctx.mesh is not None:
        def _gs(path, leaf):
            from jax.sharding import NamedSharding
            p = shard_rules._path_strs(path)
            return NamedSharding(pctx.mesh, shard_rules.spec_for(p, leaf.shape, pctx))
        params_shape = jax.eval_shape(
            lambda: T.init_params(cfg, jax.random.PRNGKey(0),
                                  dtype=jnp.dtype(tcfg.param_dtype)))
        grad_shardings = jax.tree_util.tree_map_with_path(_gs, params_shape)

    def step_fn(state: TrainState, batch):
        with par.use(pctx):
            loss, grads = _loss_and_grads(cfg, tcfg, state.params, batch,
                                          grad_shardings)
            if pctx.compress_grads and pctx.mesh is not None:
                from repro.parallel import compress
                grads = compress.compress_decompress(grads)
            new_params, new_opt, metrics = opt_mod.apply_updates(
                tcfg.opt, grads, state.opt, state.params, state.step
            )
            metrics = dict(metrics, loss=loss)
            return TrainState(new_params, new_opt, state.step + 1), metrics

    return step_fn


def state_shardings(state_shapes, pctx: par.ParallelCtx):
    return shard_rules.param_shardings(state_shapes, pctx)


def batch_shardings(batch_shapes, pctx: par.ParallelCtx):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = pctx.mesh
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]

    def one(leaf):
        # Replicate when the global batch doesn't divide the DP lanes
        # (e.g. long_500k's batch of 1).
        if leaf.shape[0] % dp != 0:
            if leaf.shape[0] > 1:
                import warnings
                warnings.warn(
                    f"batch dim {leaf.shape[0]} does not divide the {dp} DP "
                    f"lanes — REPLICATING (every lane computes the full "
                    f"batch). Check global_batch / microbatches vs mesh.",
                    stacklevel=2)
            spec = None
        else:
            spec = bspec
        return NamedSharding(mesh, P(spec, *([None] * (leaf.ndim - 1))))

    return jax.tree.map(one, batch_shapes)
