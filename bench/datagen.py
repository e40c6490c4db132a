"""Point sources of the benchmark's deployments, drawn from a seed.

A configuration's ``data`` block names one generator (``kind``) and its
parameters; ``draw(data, n, rng)`` returns (n, 2) float64 points in the
configuration's units.
"""
from __future__ import annotations

import numpy as np


def seed_spreader(data: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Gan & Tao's seed spreader at d = 2.  A spreader emits points
    uniformly in a disc of radius ``r_vicinity`` round its position; after
    every ``c_reset`` points it shifts by ``r_shift`` in a random
    direction; it restarts at a random location ``restarts`` times, at
    evenly spaced points of the run.  A ``noise`` share of points is
    uniform over the domain.  Points come out in Morton order (see
    ``morton_order``): the order a spatial partitioner hands them on."""
    lo, hi = float(data["domain"][0]), float(data["domain"][1])
    n_noise = int(round(n * float(data["noise"])))
    m = n - n_noise
    r_vic, r_shift = float(data["r_vicinity"]), float(data["r_shift"])
    c_reset, restarts = int(data["c_reset"]), int(data["restarts"])
    steps = -(-m // c_reset)
    restart_at = set(np.linspace(0, steps, restarts, endpoint=False).astype(int).tolist())
    pos = np.empty((steps, 2))
    p = rng.uniform(lo, hi, 2)
    for s in range(steps):
        if s in restart_at:
            p = rng.uniform(lo, hi, 2)
        elif s:
            a = rng.uniform(0, 2 * np.pi)
            p = np.clip(p + r_shift * np.array([np.cos(a), np.sin(a)]), lo, hi)
        pos[s] = p
    centre = np.repeat(pos, c_reset, axis=0)[:m]
    a = rng.uniform(0, 2 * np.pi, m)
    r = r_vic * np.sqrt(rng.uniform(0, 1, m))
    pts = centre + np.stack([r * np.cos(a), r * np.sin(a)], axis=-1)
    noise = rng.uniform(lo, hi, (n_noise, 2))
    out = np.clip(np.concatenate([pts, noise]), lo, hi)
    return out[morton_order(out, (lo, lo, hi, hi))]


GENERATORS = {"seed_spreader": seed_spreader}


def draw(data: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return GENERATORS[data["kind"]](data, n, rng)


def morton_code(pts: np.ndarray, bounds, bits: int = 16) -> np.ndarray:
    """Z-order code of each point on a 2**bits raster over ``bounds``."""
    x0, y0, x1, y1 = (float(b) for b in bounds)
    g = (1 << bits) - 1
    ix = np.clip((pts[:, 0] - x0) / (x1 - x0) * g, 0, g).astype(np.int64)
    iy = np.clip((pts[:, 1] - y0) / (y1 - y0) * g, 0, g).astype(np.int64)
    code = np.zeros(len(pts), np.int64)
    for b in range(bits):
        code |= ((ix >> b) & 1) << (2 * b + 1)
        code |= ((iy >> b) & 1) << (2 * b)
    return code


def morton_order(pts: np.ndarray, bounds) -> np.ndarray:
    return np.argsort(morton_code(pts, bounds), kind="stable")
