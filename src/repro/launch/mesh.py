"""Production mesh builders.

A function (not a module constant) so importing never touches jax device
state.  Single pod: 256 chips as (data=16, model=16); multi-pod: 2 pods
= 512 chips as (pod=2, data=16, model=16) — 'pod' is the outer DP axis
(its collectives cross the inter-pod DCN/ICI links, which is what the
multi-pod dry-run exercises).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes (its default is Explicit): the
    repo's shardings are propagated by the compiler, never typed."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def device_shortfall(needed: int) -> str | None:
    """None when jax sees at least ``needed`` devices, else what is
    missing and the fix for the platform it runs on: more chips on an
    accelerator, the forced host device count on the CPU."""
    devices = jax.devices()
    if len(devices) >= needed:
        return None
    platform = devices[0].platform
    msg = (f"needs {needed} devices but jax sees {len(devices)} "
           f"{platform} device(s); ")
    if platform == "cpu":
        return msg + (f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                      f"{needed} before jax initialises (or lower shards)")
    return msg + f"run on a host with >= {needed} chips (or lower shards)"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, axis: str = "data"):
    """A 1-D mesh over the first ``n`` devices (default: all of them)."""
    n = n or len(jax.devices())
    return make_mesh((n,), (axis,))
