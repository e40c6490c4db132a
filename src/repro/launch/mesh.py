"""Mesh builders.

Functions (not module constants) so importing never touches jax device
state.
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


def make_host_mesh(n: int | None = None, axis: str = "data"):
    """A 1-D mesh over the first ``n`` devices (default: all of them)."""
    n = n or len(jax.devices())
    return make_mesh((n,), (axis,))
