"""Pluggable execution backends behind the `repro.ddc.DDC` facade.

A ``Backend`` executes the paper's two-phase pipeline for one deployment
style; the facade is backend-agnostic, which is the point — the paper's
contribution is communication-model-agnostic, so switching between the
host oracle, the jitted ``shard_map`` collectives, and the streaming
delta-merge engine must be a config knob, not a caller rewrite.

* ``host``   — wraps ``repro.core.ddc.ddc_host`` (NumPy, exact
  polygon-overlap merge): the paper-faithful oracle.
* ``jit``    — wraps ``repro.core.ddc.make_ddc_fn`` over a host mesh:
  phase 1 per lane, phase 2 across the configured collective schedule.
* ``stream`` — wraps ``repro.serve.ClusterService``: ring-buffer ingest,
  dirty-shard phase 1, exact delta-merge, TTL eviction, snapshots.
* ``dist``   — wraps ``repro.serve.DistClusterService``: the same
  streaming engine with every shard's buffers pinned to its own mesh
  device (shard_map ingest/evict/phase 1); only delta ClusterSets and
  slot-map rows cross the mesh axis, so its CommMeter counts are real
  transfer bytes, not a model (DESIGN.md §10).  Needs
  ``len(jax.devices()) >= shards``.

All four consume the same per-shard membership (the block
``np.array_split`` partition), so they produce the identical global
clustering (``repro.core.ddc.same_clustering``) — asserted by
``tests/test_ddc_api.py`` / ``tests/test_dist_backend.py`` on every
``PHASE2_LAYOUTS`` layout.

Batch backends (``host``, ``jit``) support ``partial_fit`` by buffering
per-shard points and lazily re-running the full pipeline on the next
read; the streaming backends (``stream``, ``dist``) repair the global
state incrementally and support TTL eviction (``expire``).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Type

import numpy as np

from repro import obs
from repro.core import ddc as core_ddc
from repro.ddc.config import ConfigError, DDCConfig

BACKENDS: Dict[str, Type["Backend"]] = {}


def register_backend(name: str):
    """Class decorator: make ``name`` constructible via ``DDCConfig``."""
    def deco(cls):
        cls.name = name
        BACKENDS[name] = cls
        return cls
    return deco


def _query_nearest(q: np.ndarray, pts: np.ndarray, labels: np.ndarray,
                   eps: float, chunk: int = 512) -> np.ndarray:
    """DBSCAN's border rule against a frozen clustering: the label of the
    nearest *clustered* fitted point within ``eps``, else noise.  The
    same read-path semantics as ``ClusterService.query``."""
    out = np.full(len(q), -1, np.int32)
    keep = labels >= 0
    if not keep.any():
        return out
    ref = pts[keep].astype(np.float64)
    ref_lab = labels[keep]
    for off in range(0, len(q), chunk):
        block = q[off:off + chunk].astype(np.float64)
        d2 = ((block[:, None, :] - ref[None, :, :]) ** 2).sum(-1)
        j = np.argmin(d2, axis=1)
        hit = d2[np.arange(len(block)), j] <= eps * eps
        out[off:off + chunk] = np.where(hit, ref_lab[j], -1)
    return out


class Backend:
    """Execution-engine interface the facade drives (see module doc).

    ``faults`` (an optional ``repro.serve.FaultPlan``) arms the
    streaming engines' fault-injection seam for reproducible chaos
    runs; the batch backends accept and ignore it (they have no
    exchange to fault)."""

    name = "?"

    def __init__(self, cfg: DDCConfig,
                 meter: core_ddc.CommMeter | None = None,
                 faults=None):
        self.cfg = cfg
        self.meter = meter or core_ddc.CommMeter()
        self.faults = faults

    # write path
    def fit(self, points: np.ndarray, t: float | None = None) -> None:
        raise NotImplementedError

    def partial_fit(self, shard: int, batch: np.ndarray,
                    t: float | None = None) -> None:
        raise NotImplementedError

    def expire(self, t: float) -> int:
        raise ConfigError(
            f"TTL eviction needs a streaming backend ('stream' or "
            f"'dist'), not {self.name!r}")

    def tracks(self):
        """The last published ``TrackSnapshot`` (DESIGN.md §14)."""
        raise ConfigError(
            f"cluster tracking needs a streaming backend ('stream' or "
            f"'dist') with track=True, not {self.name!r}: tracking is a "
            f"fold over refresh generations, and the batch backends "
            f"have none")

    # read path
    def labels(self) -> np.ndarray:
        raise NotImplementedError

    def points(self) -> np.ndarray:
        raise NotImplementedError

    def query(self, points: np.ndarray, legacy: bool = False):
        """Label query points against the fitted clustering.  Returns a
        ``repro.serve.QueryResult`` (labels + snapshot version +
        degraded flag + routing + latency) that duck-types as the bare
        labels array; ``legacy=True`` returns the ndarray outright."""
        raise NotImplementedError

    # snapshot-versioned read path (DESIGN.md §12)
    def snapshot(self):
        """The last published immutable read view, or None."""
        raise NotImplementedError

    def read_snapshot(self):
        """Freshness-seeking read view: fold pending writes, then return
        the published snapshot (None for an empty model)."""
        raise NotImplementedError

    @property
    def quarantined(self) -> dict:
        """shard -> reason for currently quarantined shards ({} for the
        batch backends: they have no failure model)."""
        return {}

    @property
    def query_tier(self):
        """The backend's ``QueryTier``: the pipelined, coalescing,
        snapshot-serving read loop (built lazily from the config's
        queue_depth / query_bucket_min / max_staleness knobs)."""
        from repro.serve import query_tier as qt

        if getattr(self, "_tier", None) is None:
            self._tier = qt.QueryTier(
                self._tier_source(),
                max_queries=self.cfg.max_queries,
                queue_depth=self.cfg.queue_depth,
                bucket_min=self.cfg.query_bucket_min,
                max_staleness=self.cfg.max_staleness)
        return self._tier

    def _tier_source(self):
        """The snapshot source the tier reads (the backend itself for
        batch backends; the serve engine for stream/dist)."""
        return self

    def service_stats(self):
        """The typed ``ServiceStats`` contract (counters vs gauges),
        surfaced identically by every backend (DESIGN.md §12)."""
        raise NotImplementedError

    def comm_stats(self) -> dict:
        return {"backend": self.name} | self.meter.snapshot()

    # snapshot/restore
    def state(self) -> tuple[dict, dict]:
        """(arrays, manifest): everything needed to resume bit-identically."""
        raise NotImplementedError

    def load_state(self, arrays: dict, manifest: dict) -> None:
        raise NotImplementedError


class _BufferedBatchBackend(Backend):
    """Shared machinery for the batch backends: per-shard point buffers,
    lazy refit, block-partition bookkeeping."""

    def __init__(self, cfg: DDCConfig, meter=None, faults=None):
        super().__init__(cfg, meter, faults=faults)
        self._shard_pts: List[np.ndarray] = [
            np.zeros((0, 2), np.float32) for _ in range(cfg.shards)]
        self._labels: Optional[np.ndarray] = None
        self._snapshot = None
        self._snapshot_version = 0
        self.refits = 0           # monotonic: full-pipeline recomputes
        # ``ServiceCounters.phase1_*``: summed over every lane's phase 1
        # (``jit``; the ``host`` oracle leaves them 0).
        from repro.serve.cluster_service import PHASE1_COUNTERS

        self.phase1_counts = dict.fromkeys(PHASE1_COUNTERS, 0)

    def fit(self, points: np.ndarray, t: float | None = None) -> None:
        pts = np.asarray(points, np.float32).reshape(-1, 2)
        parts = np.array_split(np.arange(len(pts)), self.cfg.shards)
        self._shard_pts = [pts[idx] for idx in parts]
        self._labels = None
        self._snapshot = None

    def partial_fit(self, shard, batch, t=None) -> None:
        if not 0 <= shard < self.cfg.shards:
            raise ConfigError(f"shard {shard} out of range [0, {self.cfg.shards})")
        batch = np.asarray(batch, np.float32).reshape(-1, 2)
        self._shard_pts[shard] = np.concatenate([self._shard_pts[shard], batch])
        self._labels = None
        self._snapshot = None

    def points(self) -> np.ndarray:
        return (np.concatenate(self._shard_pts) if any(len(p) for p in self._shard_pts)
                else np.zeros((0, 2), np.float32))

    def parts(self) -> List[np.ndarray]:
        out, base = [], 0
        for p in self._shard_pts:
            out.append(np.arange(base, base + len(p)))
            base += len(p)
        return out

    def labels(self) -> np.ndarray:
        if self._labels is None:
            self._labels = self._refit()
            self.refits += 1
        return self._labels

    def query(self, points: np.ndarray, legacy: bool = False):
        """Label queries via the published-snapshot path (the DESIGN.md
        §12 fix for the silent full-pipeline recompute per call): the
        first read after a write refits ONCE and publishes a snapshot;
        every further query is answered from it — O(points), one bounded
        batched kernel, no recompute (the ``refits`` counter proves it).
        """
        res = self.query_tier.query(points)
        return res.labels if legacy else res

    # -- snapshot publish (the batch edition of the serve engines') --------

    def snapshot(self):
        # A write since the last publish invalidates (fit/partial_fit
        # set _snapshot = None), so a held snapshot is never torn.
        return self._snapshot

    def read_snapshot(self):
        if not any(len(p) for p in self._shard_pts):
            return None
        if self._snapshot is None:
            self._publish_snapshot()
        return self._snapshot

    def _publish_snapshot(self):
        """Cut an immutable read view from the buffered shard points +
        (lazily recomputed) labels: pow2-padded (K, cap) buffers, global
        labels per slot, per-shard live bboxes — the same layout the
        serve engines publish, so one QueryTier serves all four
        backends bit-identically."""
        import jax.numpy as jnp

        from repro.serve import query_tier as qt

        labels = self.labels()          # refits at most once per write
        k = self.cfg.shards
        lens = [len(p) for p in self._shard_pts]
        cap = max(16, 1 << (max(lens) - 1).bit_length())
        pts = np.zeros((k, cap, 2), np.float32)
        mask = np.zeros((k, cap), bool)
        glab = np.full((k, cap), -1, np.int32)
        bboxes = []
        base = 0
        for s, p in enumerate(self._shard_pts):
            pts[s, :len(p)] = p
            mask[s, :len(p)] = True
            glab[s, :len(p)] = labels[base:base + len(p)]
            base += len(p)
            bboxes.append(
                (float(p[:, 0].min()), float(p[:, 1].min()),
                 float(p[:, 0].max()), float(p[:, 1].max()))
                if len(p) else None)
        self._snapshot_version += 1
        self._snapshot = qt.Snapshot(
            version=self._snapshot_version,
            epoch=self.refits,
            published_at=time.monotonic(),
            eps=float(self.cfg.eps),
            pts=jnp.asarray(pts), mask=jnp.asarray(mask),
            glabels=jnp.asarray(glab),
            bboxes=tuple(bboxes),
            quarantined=frozenset(),
            n_live=sum(lens),
            n_clusters=len(set(labels[labels >= 0].tolist())),
        )
        return self._snapshot

    def service_stats(self):
        from repro.serve import query_tier as qt

        tier = getattr(self, "_tier", None)
        tc = tier.counters() if tier is not None else {}
        labels = self.labels() if any(len(p) for p in self._shard_pts) \
            else np.zeros((0,), np.int32)
        counters = qt.ServiceCounters(
            refreshes=self.refits,
            refits=self.refits,
            snapshots_published=self._snapshot_version,
            queries_served=tc.get("queries_served", 0),
            query_launches=tc.get("query_launches", 0),
            coalesced_requests=tc.get("coalesced_requests", 0),
            query_rows=tc.get("query_rows", 0),
            deadline_misses=tc.get("deadline_misses", 0),
            degraded_queries=tc.get("degraded_queries", 0),
            **self.phase1_counts,
        )
        gauges = qt.ServiceGauges(
            shards=self.cfg.shards,
            capacity=int(self._snapshot.pts.shape[1])
            if self._snapshot is not None else 0,
            n_live=sum(len(p) for p in self._shard_pts),
            n_clusters=len(set(labels[labels >= 0].tolist())),
            snapshot_version=self._snapshot_version,
            snapshot_epoch=self._snapshot.epoch
            if self._snapshot is not None else 0,
            queue_pending=tier.pending if tier is not None else 0,
            jit_cache_entries=qt.snapshot_query_cache_entries(),
        )
        return qt.ServiceStats(backend=self.name, counters=counters,
                               gauges=gauges, comm=self.meter.snapshot())

    def _refit(self) -> np.ndarray:
        raise NotImplementedError

    def comm_stats(self) -> dict:
        self.labels()     # the meter fills when the (lazy) pipeline runs
        return super().comm_stats()

    def state(self) -> tuple[dict, dict]:
        arrays = {f"shard_{s}": p for s, p in enumerate(self._shard_pts)}
        arrays["labels"] = self.labels()
        return arrays, {"n_shards": self.cfg.shards}

    def load_state(self, arrays, manifest) -> None:
        self._shard_pts = [np.asarray(arrays[f"shard_{s}"], np.float32)
                           for s in range(int(manifest["n_shards"]))]
        self._labels = np.asarray(arrays["labels"], np.int32)
        self._snapshot = None


@register_backend("host")
class HostBackend(_BufferedBatchBackend):
    """Paper-faithful NumPy reference: per-partition ``dbscan_ref`` +
    exact polygon-overlap union-find (``ddc_host``, grid contours)."""

    def __init__(self, cfg: DDCConfig, meter=None, faults=None):
        super().__init__(cfg, meter, faults=faults)
        self._exchanged = 0

    def _refit(self) -> np.ndarray:
        pts = self.points()
        parts = self.parts()
        if len(pts) == 0:
            return np.zeros((0,), np.int32)
        labels, _, exchanged = core_ddc.ddc_host(
            pts, len(parts), self.cfg.eps, self.cfg.min_pts,
            partition=parts, contour="grid")
        self._exchanged = int(exchanged)
        # Contour vertices are the only phase-2 traffic (the 1–2 % claim):
        # each crosses once as an (x, y) f32 pair.
        self.meter.add_collective(1, self._exchanged * 8)
        self.meter.add_merge(len(parts), self.cfg.max_clusters)
        return labels.astype(np.int32)

    def comm_stats(self) -> dict:
        return super().comm_stats() | {"contour_vertices": self._exchanged}

    def state(self) -> tuple[dict, dict]:
        arrays, manifest = super().state()
        # labels() ran inside super().state(), so the counter is current;
        # a restored model must report it without re-running the fit.
        return arrays, manifest | {"exchanged": self._exchanged}

    def load_state(self, arrays, manifest) -> None:
        super().load_state(arrays, manifest)
        self._exchanged = int(manifest.get("exchanged", 0))


@register_backend("jit")
class JitBackend(_BufferedBatchBackend):
    """Jitted ``shard_map`` pipeline over a host mesh: zero-communication
    phase 1 per lane, then the configured collective schedule (sync
    all-gather / async butterfly / tree) for phase 2.

    Per-shard buffers are padded to a common static width so the mesh
    sees exactly the block partition the other backends use; the padding
    mask keeps padded rows out of phase 1.
    """

    def __init__(self, cfg: DDCConfig, meter=None, faults=None):
        super().__init__(cfg, meter, faults=faults)
        self._runners: dict = {}
        self._meshes: dict = {}

    def make_runner(self, n_points: int):
        """The jitted distributed entry point for ``n_points`` inputs
        ((n, 2) + (n,) mask, sharded over the mesh).  Exposed for the
        benchmarks/dry-runs that lower + compile it explicitly;
        ``n_points`` must be a multiple of ``shards``."""
        from repro.launch import mesh as mesh_mod

        k = self.cfg.shards
        if n_points % k:
            raise ConfigError(f"n_points {n_points} not a multiple of shards {k}")
        shortfall = mesh_mod.device_shortfall(k)
        if shortfall:
            raise ConfigError(f"jit backend at shards={k} {shortfall}")
        key = n_points
        if key not in self._runners:
            if len(self._runners) >= 4:   # drop stale executables: every
                self._runners.clear()     # distinct width is a recompile
                self._meshes.clear()
            mesh = mesh_mod.make_host_mesh(k)
            self._meshes[key] = mesh
            self._runners[key] = core_ddc.make_ddc_fn(
                mesh, "data", self.cfg.core(), self.meter)
        return self._runners[key]

    def _refit(self) -> np.ndarray:
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.serve import cluster_service

        k = self.cfg.shards
        lens = [len(p) for p in self._shard_pts]
        if sum(lens) == 0:
            return np.zeros((0,), np.int32)
        # Round the padded width up so a partial_fit-driven trickle of
        # growth re-uses one compiled program instead of recompiling the
        # whole shard_map pipeline at every new max-shard length.
        cap = max(lens)
        cap = max(16, 1 << (cap - 1).bit_length())
        with obs.span("ddc.refit", backend=self.name, shards=k, cap=cap):
            padded = np.zeros((k, cap, 2), np.float32)
            mask = np.zeros((k, cap), bool)
            for s, p in enumerate(self._shard_pts):
                padded[s, :len(p)] = p
                mask[s, :len(p)] = True
            run = self.make_runner(k * cap)
            # Each lane's block straight to its own device.
            mesh = self._meshes[k * cap]
            x = jax.device_put(padded.reshape(k * cap, 2),
                               NamedSharding(mesh, P("data", None)))
            m = jax.device_put(mask.reshape(k * cap),
                               NamedSharding(mesh, P("data")))
            with obs.span("ddc.run") as attrs:
                glabels, gcs, _, lanes = run(x, m)
                flat, overflow, (st, cut) = jax.device_get(
                    (glabels, gcs.overflow, lanes))
                per_lane = [cluster_service.count_phase1(
                    self.phase1_counts, core_ddc.Phase1Stats(*leaves))
                    for leaves in zip(*st)]
                for key in per_lane[0]:
                    attrs[key] = [lane[key] for lane in per_lane]
                attrs["overflow"] = bool(overflow)
                attrs["truncated"] = int(st.truncated.sum() + cut.sum())
            flat = flat.reshape(k, cap)
            return np.concatenate(
                [flat[s, :n] for s, n in enumerate(lens)]).astype(np.int32)


@register_backend("stream")
class StreamBackend(Backend):
    """The online serve engine: ring-buffer ingest, dirty-shard phase 1,
    exact delta-merge, bbox-routed point queries, TTL eviction, and
    bit-identical snapshot/restore.  ``fit`` streams the batch in;
    ``partial_fit`` is the native write path."""

    def __init__(self, cfg: DDCConfig, meter=None, faults=None):
        super().__init__(cfg, meter, faults=faults)
        self._svc = None

    @classmethod
    def _svc_cls(cls):
        from repro.serve import ClusterService

        return ClusterService

    @property
    def service(self):
        """The underlying service engine (lazily built: the ring
        capacity may be sized by the first ``fit``)."""
        if self._svc is None:
            if self.cfg.capacity is None:
                raise ConfigError(
                    f"backend={self.name!r} with partial_fit before fit "
                    f"needs an explicit capacity in DDCConfig (fit() would "
                    f"size it from the batch)")
            self._svc = self._build(self.cfg.capacity)
        return self._svc

    def _stream_config(self, capacity: int):
        from repro.serve import StreamConfig

        return StreamConfig(
            shards=self.cfg.shards, capacity=capacity,
            max_batch=min(self.cfg.max_batch, capacity),
            max_queries=self.cfg.max_queries,
            merge_mode=self.cfg.merge_mode,
            max_retries=self.cfg.max_retries,
            retry_backoff=self.cfg.retry_backoff,
            journal_limit=self.cfg.journal_limit,
            agg_degree=self.cfg.agg_degree,
            track=self.cfg.track,
            track_history=self.cfg.track_history,
            match_min_overlap=self.cfg.match_min_overlap,
            ddc=self.cfg.core())

    def _build(self, capacity: int):
        return self._svc_cls()(self._stream_config(capacity),
                               meter=self.meter, faults=self.faults)

    def fit(self, points: np.ndarray, t: float | None = None) -> None:
        from repro.data import spatial

        pts = np.asarray(points, np.float32).reshape(-1, 2)
        k = self.cfg.shards
        cap = self.cfg.capacity or spatial.shard_capacity(len(pts), k)
        self._svc = self._build(cap)
        batch = min(self.cfg.max_batch, cap)
        for shard, chunk in spatial.stream_batches(pts, k, batch):
            self._svc.ingest(shard, chunk, t=t)
        self._svc.refresh()

    def partial_fit(self, shard, batch, t=None) -> None:
        self.service.ingest(shard, batch, t=t)

    def expire(self, t: float) -> int:
        return sum(self.service.evict_older_than(s, t)
                   for s in range(self.cfg.shards))

    def tracks(self):
        if not self.cfg.track:
            raise ConfigError(
                "cluster tracking is disabled for this model; construct "
                "with DDCConfig(track=True, backend='stream'|'dist') to "
                "assign stable track IDs at refresh")
        # Freshness-seeking like read_snapshot: fold pending writes so
        # the returned TrackSnapshot reflects everything ingested.
        self.service.read_snapshot()
        return self.service.track_snapshot()

    def labels(self) -> np.ndarray:
        _, _, labels = self.service.live()
        return labels

    def points(self) -> np.ndarray:
        pts, _, _ = self.service.live()
        return pts

    def parts(self) -> List[np.ndarray]:
        _, parts, _ = self.service.live()
        return parts

    def query(self, points: np.ndarray, legacy: bool = False):
        return self.service.query(points, legacy=legacy)

    # -- snapshot-versioned reads (delegate to the serve engine) -----------

    def snapshot(self):
        return self._svc.snapshot() if self._svc is not None else None

    def read_snapshot(self):
        if self._svc is None and self.cfg.capacity is None:
            return None          # nothing fitted, nothing to publish
        return self.service.read_snapshot()

    @property
    def quarantined(self) -> dict:
        return self._svc.quarantined if self._svc is not None else {}

    def service_stats(self):
        from repro.serve import query_tier as qt

        tier = getattr(self, "_tier", None)
        if self._svc is None:
            return qt.ServiceStats(
                backend=self.name, counters=qt.ServiceCounters(),
                gauges=qt.ServiceGauges(shards=self.cfg.shards),
                comm=self.meter.snapshot())
        return self.service.service_stats(tier=tier)

    def comm_stats(self) -> dict:
        # Derived from the typed contract so the dict view can't drift;
        # same flat shape as ever (backend tag + service stats + meter).
        if self._svc is None:
            return {"backend": self.name} | self.meter.snapshot()
        return self.service_stats().comm_dict()

    def state(self) -> tuple[dict, dict]:
        return self.service.state_dict()

    def load_state(self, arrays, manifest) -> None:
        from repro.serve import StreamConfig

        scfg = StreamConfig(
            shards=int(manifest["shards"]),
            capacity=int(manifest["capacity"]),
            max_batch=int(manifest["max_batch"]),
            max_queries=int(manifest["max_queries"]),
            merge_mode=manifest["merge_mode"],
            max_retries=int(manifest.get("max_retries",
                                         self.cfg.max_retries)),
            retry_backoff=float(manifest.get("retry_backoff",
                                             self.cfg.retry_backoff)),
            journal_limit=int(manifest.get("journal_limit",
                                           self.cfg.journal_limit)),
            agg_degree=manifest.get("agg_degree", self.cfg.agg_degree),
            track=bool(manifest.get("track", self.cfg.track)),
            track_history=int(manifest.get("track_history",
                                           self.cfg.track_history)),
            match_min_overlap=float(manifest.get("match_min_overlap",
                                                 self.cfg.match_min_overlap)),
            ddc=self.cfg.core())
        self._svc = self._svc_cls().from_state(
            scfg, arrays, manifest, meter=self.meter, faults=self.faults)


@register_backend("dist")
class DistBackend(StreamBackend):
    """The device-resident streaming engine: the ``stream`` control
    plane over a ``shard_map`` data plane that pins each shard's ring
    buffers to its own mesh device.  Ingest/evict/dirty-shard phase 1
    run lane-local; only delta ClusterSets (up) and slot-map rows
    (down) cross the mesh axis, so ``comm_stats()`` reports *real*
    axis-crossing bytes.  Bit-identical to ``stream`` (and ``host``) on
    the same call sequence; snapshots are interchangeable with the
    ``stream`` backend's.  Requires ``len(jax.devices()) >= shards``
    (``XLA_FLAGS=--xla_force_host_platform_device_count=K`` on CPU)."""

    @classmethod
    def _svc_cls(cls):
        from repro.serve import DistClusterService

        return DistClusterService
