"""K-Means (Lloyd) — the paper's second local-clustering algorithm.

DDC is algorithm-agnostic in phase 1; the paper evaluates both K-Means
and DBSCAN.  This is a masked, static-shape JAX implementation with
k-means++ seeding, used by the data-curation pipeline (embedding
clustering) and by DDC when cfg.local_algo == "kmeans".
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops


class KMeansResult(NamedTuple):
    labels: jax.Array      # (n,) int32
    centroids: jax.Array   # (k, d)
    inertia: jax.Array     # () f32


def kmeanspp_init(key: jax.Array, points: jax.Array, mask: jax.Array, k: int) -> jax.Array:
    """k-means++ seeding on a masked buffer (vectorised, O(k·n·d))."""
    n, d = points.shape
    big = 1e30

    def pick(key, weights):
        return jax.random.categorical(key, jnp.log(jnp.maximum(weights, 1e-30)))

    k0, key = jax.random.split(key)
    first = pick(k0, mask.astype(jnp.float32))
    cents = jnp.zeros((k, d), points.dtype).at[0].set(points[first])
    d2 = jnp.where(mask, jnp.sum((points - points[first]) ** 2, -1), 0.0)

    def body(i, state):
        key, cents, d2 = state
        ki, key = jax.random.split(key)
        nxt = pick(ki, d2)
        cents = cents.at[i].set(points[nxt])
        nd = jnp.where(mask, jnp.sum((points - points[nxt]) ** 2, -1), 0.0)
        return key, cents, jnp.minimum(d2, nd)

    _, cents, _ = jax.lax.fori_loop(1, k, body, (key, cents, d2))
    return cents


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(
    key: jax.Array, points: jax.Array, mask: jax.Array, k: int, iters: int = 25
) -> KMeansResult:
    cents = kmeanspp_init(key, points, mask, k)

    def step(cents, _):
        d2 = ops.pairwise_dist_sq(points, cents)           # (n, k)
        d2 = jnp.where(mask[:, None], d2, 0.0)
        labels = jnp.argmin(d2, axis=1)
        onehot = jax.nn.one_hot(labels, k, dtype=points.dtype) * mask[:, None]
        sums = jnp.matmul(onehot.T, points,                 # (k, d)
                          precision=jax.lax.Precision.HIGHEST)
        cnts = jnp.sum(onehot, axis=0)[:, None]
        new = jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1), cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    d2 = ops.pairwise_dist_sq(points, cents)
    labels = jnp.where(mask, jnp.argmin(d2, axis=1), -1).astype(jnp.int32)
    inertia = jnp.sum(jnp.where(mask, jnp.min(d2, axis=1), 0.0))
    return KMeansResult(labels, cents, inertia)
