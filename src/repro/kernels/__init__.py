"""Pallas TPU kernels for the perf-critical hot-spots, with jnp oracles.

- pairwise_dist: DDC/DBSCAN ε-neighbour counting + min-label sweeps (VPU)
- contour_dist: DDC phase-2 slot×slot contour min-distance merge matrix
- flash_attention: tiled online-softmax attention (GQA via index_map)
- ssd_scan: Mamba-2 state-space-duality chunked scan

Use ``repro.kernels.ops`` — it pads, dispatches pallas/ref by backend,
and is what the model stack calls.
"""
from . import ops, ref  # noqa: F401
