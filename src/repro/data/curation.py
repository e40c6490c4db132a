"""DDC-driven data curation — the paper's clustering as a first-class
feature of the training framework (DESIGN.md §4).

Documents are embedded (here: provided 2-D embeddings; in production,
any encoder) and clustered with *distributed* DDC on the training mesh:
each data shard clusters its local embeddings (phase 1, zero comm), the
1–2 % contour representatives are hierarchically merged (phase 2), and
the resulting global clusters drive:

* cluster-balanced sampling weights (upweight rare clusters), and
* dedup candidates (documents in the same dense cluster core).

This is exactly the paper's pitch — analyse big data where it lives,
exchange only representatives — applied to LM data pipelines.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import ddc
from repro.data.pipeline import DataConfig


@dataclasses.dataclass
class CurationResult:
    labels: np.ndarray          # (n_docs,) global cluster id (-1 noise)
    n_clusters: int
    cluster_sizes: np.ndarray
    sample_weights: np.ndarray  # per-cluster balanced sampling weights
    exchanged_fraction: float   # bytes exchanged / raw embedding bytes


def curate(
    embeddings: np.ndarray,
    mesh=None,
    axis: str = "data",
    cfg: ddc.DDCConfig | None = None,
    temperature: float = 0.5,
) -> CurationResult:
    """Cluster document embeddings with DDC and derive sampling weights.

    With a mesh: distributed shard_map DDC across ``axis``; without: the
    host path.  Weights ∝ (1 / cluster_size)^temperature, normalised —
    temperature=0 keeps natural frequency, 1 is fully balanced.
    """
    n = len(embeddings)
    cfg = cfg or ddc.DDCConfig(
        eps=0.04, min_pts=4, grid=128, max_clusters=64, max_verts=64
    )
    if mesh is not None:
        k = mesh.shape[axis]
        pad = (-n) % k
        pts = np.pad(embeddings, ((0, pad), (0, 0)))
        mask = np.arange(len(pts)) < n
        run = ddc.make_ddc_fn(mesh, axis, cfg)
        glabels, gcs, _, _ = run(jnp.asarray(pts), jnp.asarray(mask))
        labels = np.asarray(glabels)[:n]
        wire = cfg.buffer_bytes() * (k.bit_length() - 1 if cfg.schedule == "async" else k - 1)
        exchanged = wire / (n * embeddings.itemsize * embeddings.shape[1])
    else:
        labels, polys, exch_pts = ddc.ddc_host(
            embeddings, 8, eps=cfg.eps, min_pts=cfg.min_pts
        )
        exchanged = exch_pts / max(n, 1)

    ids = sorted(set(labels[labels >= 0]))
    remap = {c: i for i, c in enumerate(ids)}
    labels = np.array([remap.get(l, -1) for l in labels])
    sizes = np.bincount(labels[labels >= 0], minlength=len(ids)).astype(np.float64)
    w = (1.0 / np.maximum(sizes, 1)) ** temperature
    w = w / w.sum() if len(w) else np.ones(1)
    return CurationResult(
        labels=labels,
        n_clusters=len(ids),
        cluster_sizes=sizes,
        sample_weights=w,
        exchanged_fraction=float(exchanged),
    )


def apply_to_data_config(dcfg: DataConfig, result: CurationResult,
                         doc_clusters: np.ndarray) -> DataConfig:
    """Map DDC clusters onto the synthetic pipeline's latent clusters and
    install balanced weights."""
    k = dcfg.n_latent_clusters
    weights = np.ones(k)
    for latent in range(k):
        members = result.labels[doc_clusters == latent]
        members = members[members >= 0]
        if len(members):
            ddc_cluster = np.bincount(members).argmax()
            weights[latent] = result.sample_weights[ddc_cluster]
    weights /= weights.sum()
    return dataclasses.replace(dcfg, curation_weights=weights)
