"""Streaming DDC serve engine: incremental ingest, delta-merge, queries.

The paper's two-phase split (local clustering, then contour-only
aggregation) is what makes an *online* clustering service cheap: when new
points land on one shard, only that shard's local clusters change, and
the global view is repaired by re-merging just the touched contours — no
bulk data exchange.  This module is that serving path, split into two
halves (DESIGN.md §10):

* **Control plane** (``ShardControlPlane``) — the host-mirror half every
  engine shares: ring slot choice, liveness/ts/seq mirrors, eviction
  victim selection, dirty-shard tracking, per-shard live-point bbox
  mirrors (query routing), and shard-range validation.  Everything the
  control plane decides is a pure function of the call sequence, so no
  device sync ever sits on the write path.
* **Data plane** — where the buffers live and kernels run.  This module's
  ``ClusterService`` keeps them host-driven on the default device (one
  process, K logical shards).  ``serve/dist_service.py`` pins each
  shard's buffers to its own mesh device and runs the same control plane
  over a ``shard_map`` data plane.

Engine behaviour (shared by both data planes):

* **Ingest buffers** — every shard owns a static-shape ring buffer
  ((capacity, 2) points + live mask), donated to the jitted append kernel
  so updates are in-place on device.  Appending past capacity evicts the
  oldest points (ring overwrite); ``evict_oldest`` (by ingest sequence)
  and ``evict_older_than`` (TTL: by the per-point ingest timestamps
  mirrored on the host) are the explicit eviction APIs — liveness holes
  are legal, the live mirror is authoritative.  The append kernel is a
  single static-shape scatter; the *slots* it writes are chosen on the
  host mirrors (dead slots in ring order first, then the oldest live
  points once the buffer is genuinely full).
* **Dirty-shard phase 1** — ``refresh`` re-runs ``ddc.local_phase`` only
  on shards whose buffers changed since the last refresh; an emptied
  shard short-circuits to the cached ``ddc.empty_clusterset`` without
  touching the device.
* **Delta-merge phase 2** — the engine caches the per-shard ClusterSets
  *and* the (K·C, K·C) slot×slot contour-distance matrix behind
  ``ddc.merge_many``.  A delta refresh recomputes only the dirty shards'
  rows/columns and re-closes the transitive closure (``ddc.merge_delta``).
  This is **exact**, not approximate: the matrix is a pure per-slot-pair
  function of the per-shard contours, so patching dirty rows/columns
  reproduces the from-scratch matrix bit-for-bit, and everything
  downstream (components, ranking, contour rebuild) is a deterministic
  function of (batch, matrix).  In particular, evictions that *split* a
  global cluster are handled correctly — the closure is always recomputed
  over per-shard contours, never over the (unsplittable) merged global
  contour.  DESIGN.md §8.
* **Queries** — ``query`` maps read-traffic points to global cluster ids:
  nearest clustered live point within ``eps`` (DBSCAN's border rule
  applied to the frozen clustering), else noise.  Query chunks are
  *routed*: only shards whose ε-dilated live bbox could contain a
  neighbour of some chunk point are scanned (the control plane mirrors
  each shard's bbox), and the scanned-shard counters surface in
  ``stats()``/``comm_stats()``.  Routing is exact — a skipped shard holds
  no point within ε of any query, so it could never supply a label.
* **Snapshot/restore** — ``state_dict``/``from_state`` serialise the
  full engine state (ring buffers, host mirrors, per-shard ClusterSets,
  pair-d2 cache); the global set/maps/labels are recomputed on restore
  from the saved inputs, so a restarted server resumes bit-identically
  without a re-cluster (DESIGN.md §9).

Communication model (``CommMeter``): shards and the aggregator are
distinct nodes.  A full re-merge ships all K ClusterSets up
(K·B bytes, B = ``DDCConfig.buffer_bytes()``); a delta refresh ships only
the dirty ones (|dirty|·B).  Both ship each shard its (C,) slot-map row
back down (K·C·4 bytes).  Steady-state single-shard ingest therefore
moves B + K·C·4 per refresh vs K·B + K·C·4 — the measurable
minimal-communication claim (tests/test_serve_stream.py).  For this host-driven
engine the model is metered; the ``dist`` data plane realises the same
byte counts as real device-boundary transfers.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import ddc
from repro.serve import faults as faults_mod
from repro.serve import hierarchy
from repro.serve import journal as journal_mod
from repro.serve import query_tier as qt
from repro.serve import tracking as tracking_mod


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static configuration of the streaming engine."""

    shards: int                     # K logical shards
    capacity: int                   # per-shard point-buffer slots
    max_batch: int = 256            # static ingest width (host pads)
    max_queries: int = 256          # static query width (host pads)
    merge_mode: str = "delta"       # "delta" | "full"
    max_retries: int = 2            # delta re-deliveries per refresh
    retry_backoff: float = 0.0      # seconds; doubles per retry round
    journal_limit: int = 1024       # per-shard WAL entries before compaction
    agg_degree: Optional[int] = None  # None: flat aggregator; >=2: tree fan-in
    track: bool = False             # cluster tracking fold (DESIGN.md §14)
    track_history: int = 16         # per-track motion-history ring length
    match_min_overlap: float = 0.0  # tighten the match gate, in [0, 1)
    ddc: ddc.DDCConfig = dataclasses.field(default_factory=ddc.DDCConfig)


# Phase-1 counters of ``ServiceCounters``, kept per service.
PHASE1_COUNTERS = ("phase1_runs", "phase1_sweeps", "phase1_doubling_steps",
                   "phase1_tile_pairs_active", "phase1_tile_pairs",
                   "phase1_dense_fallbacks")


def count_phase1(counts: dict, st: ddc.Phase1Stats) -> dict:
    """Fold one phase-1 run's host-side stats into ``counts`` (keyed by
    ``PHASE1_COUNTERS``); returns them as span attributes."""
    attrs = {"sweeps": int(st.sweeps),
             "doubling_steps": int(st.doubling_steps),
             "tile_pairs_active": int(st.tile_pairs_active),
             "tile_pairs": int(st.tile_pairs),
             "dense_fallback": bool(st.dense_fallback)}
    counts["phase1_runs"] += 1
    counts["phase1_sweeps"] += attrs["sweeps"]
    counts["phase1_doubling_steps"] += attrs["doubling_steps"]
    counts["phase1_tile_pairs_active"] += attrs["tile_pairs_active"]
    counts["phase1_tile_pairs"] += attrs["tile_pairs"]
    counts["phase1_dense_fallbacks"] += int(attrs["dense_fallback"])
    return attrs


# ---------------------------------------------------------------------------
# Jitted state-update kernels (static shapes; buffers donated)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _append(pts_buf, mask_buf, batch, idx, nb):
    """Ring-buffer append: scatter the ``nb`` valid rows of ``batch``
    into slots ``idx`` (bmax,) and mark them live, in place.

    The *choice* of slots happens on the host mirrors (``_write_slots``):
    dead slots in ring order first, then — only when the buffer is
    genuinely full — the oldest live points.  The kernel itself is a
    plain static-shape scatter, so one compilation serves the contiguous
    case, the wraparound case, and rings with TTL holes alike.
    """
    cap = pts_buf.shape[0]
    bmax = batch.shape[0]
    wvalid = jnp.arange(bmax) < nb
    safe = jnp.where(wvalid, idx, cap)               # invalid rows drop
    pts_buf = pts_buf.at[safe].set(batch, mode="drop")
    mask_buf = mask_buf.at[safe].set(True, mode="drop")
    return pts_buf, mask_buf


@functools.partial(jax.jit, donate_argnums=(0,))
def _kill_mask(mask_buf, kill):
    """Clear the live bit of every slot marked in ``kill`` (cap,) bool.
    One kernel serves every eviction flavour — oldest-n, TTL, clear —
    because the *choice* of victims is made on the host mirrors (ingest
    order and timestamps are a pure function of the call sequence, no
    device sync needed)."""
    return mask_buf & ~kill


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_row(stack, row, i):
    """stack[i] <- row for every leaf of a stacked pytree (in place)."""
    return jax.tree.map(
        lambda s, x: jax.lax.dynamic_update_slice(
            s, x[None], (i,) + (0,) * x.ndim),
        stack, row)


@jax.jit
def _global_labels(dense, mask, maps):
    """(K, cap) dense local labels + (K, C) slot maps -> global labels."""
    def one(d, m, mp):
        return jnp.where(m & (d >= 0), mp[jnp.clip(d, 0)], -1)
    with jax.named_scope("p2.labels"):
        return jax.vmap(one)(dense, mask, maps)


@jax.jit
def _query_labels(q, qn, pts, mask, glabels, eps):
    """Nearest clustered live point within eps, else -1.  q: (Qmax, 2);
    ``pts``/``mask``/``glabels`` carry a leading scanned-shard axis (any
    width: the router stacks only candidate shards, padded rows masked)."""
    flat = pts.reshape(-1, 2)
    ok = (mask & (glabels >= 0)).reshape(-1)
    d2 = jnp.sum((q[:, None, :] - flat[None, :, :]) ** 2, axis=-1)
    d2 = jnp.where(ok[None, :], d2, jnp.float32(1e30))
    j = jnp.argmin(d2, axis=1)
    hit = d2[jnp.arange(q.shape[0]), j] <= eps * eps
    lab = jnp.where(hit, glabels.reshape(-1)[j], -1)
    return jnp.where(jnp.arange(q.shape[0]) < qn, lab, -1)


def _cs_to_host(cs: ddc.ClusterSet, stats: ddc.Phase1Stats | None = None
                ) -> Tuple[dict, Optional[ddc.Phase1Stats]]:
    """One shard's delta as the host-side wire payload the validation
    gate (and the fault seam) sees: plain numpy views of the leaves.
    The shard's phase-1 ``stats``, if any, come back in the same host
    copy: returns (payload, stats as numpy scalars or None)."""
    host_cs, host_stats = jax.device_get((cs, stats))
    return host_cs._asdict(), host_stats


def _cs_from_host(payload: dict) -> ddc.ClusterSet:
    """Rebuild the device ClusterSet from the wire payload.  The
    host round-trip is bit-exact (no dtype changes), so staging the
    gated payload — not the pre-seam device value — costs nothing."""
    return ddc.ClusterSet(
        contours=jnp.asarray(payload["contours"], jnp.float32),
        counts=jnp.asarray(payload["counts"], jnp.int32),
        sizes=jnp.asarray(payload["sizes"], jnp.int32),
        valid=jnp.asarray(payload["valid"], bool),
        overflow=jnp.asarray(payload["overflow"], bool),
    )


# ---------------------------------------------------------------------------
# Control plane — the host-mirror half every data plane shares
# ---------------------------------------------------------------------------


class ShardControlPlane:
    """Host mirrors + write/evict/routing policy over K logical shards.

    Subclasses supply the data plane: ``_append_chunk`` (write one padded
    chunk into a shard's device buffer), ``_kill_device`` (clear live
    bits on device), ``_read_view`` (donation-safe copies for snapshot
    publish), and ``_invalidate_reads``.  Everything else
    — slot choice, eviction victim selection, TTL stamps, bbox mirrors,
    dirty tracking, shard-range validation, snapshot publish/swap — is
    shared host logic that never syncs with the device on the write path.
    """

    flavor = "base"                 # backend tag ("stream" / "dist")

    def __init__(self, scfg: StreamConfig, meter: ddc.CommMeter | None = None,
                 faults: faults_mod.FaultPlan | None = None):
        if scfg.merge_mode not in ("delta", "full"):
            raise ValueError(scfg.merge_mode)
        if scfg.capacity < scfg.max_batch:
            raise ValueError(
                f"capacity {scfg.capacity} < max_batch {scfg.max_batch}: an "
                f"append chunk could overwrite itself in the ring scatter")
        self.scfg = scfg
        self.cfg = scfg.ddc
        self.meter = meter
        self.faults = faults
        k, cap = scfg.shards, scfg.capacity
        # Host mirrors of the ring state (known exactly from the call
        # sequence — no device sync on the write path).  ``_live`` is the
        # authoritative liveness mirror (TTL eviction punches holes, so
        # head/count alone no longer describe the live set); ``_ts`` and
        # ``_seq`` stamp each slot with its ingest timestamp and global
        # ingest sequence number for TTL / oldest-first eviction.
        # ``_hpts`` mirrors the coordinates the control plane itself
        # wrote (ingest sees every point on the host), which is what
        # keeps the per-shard bbox exact across evictions without ever
        # reading the device buffers back.
        self._head = [0] * k
        self._count = [0] * k
        self._live = [np.zeros((cap,), bool) for _ in range(k)]
        self._ts = [np.full((cap,), -np.inf) for _ in range(k)]
        self._seq = [np.full((cap,), -1, np.int64) for _ in range(k)]
        self._hpts = [np.zeros((cap, 2), np.float32) for _ in range(k)]
        self._bbox: List[Optional[tuple]] = [None] * k
        self._next_seq = 0
        self._dirty = set(range(k))
        # Aggregator mirror: the control plane caches every shard's last
        # exchanged ClusterSet (stacked), the slot-distance matrix, and
        # the merged global state — the state a delta refresh patches.
        empty = ddc.empty_clusterset(self.cfg)
        self._local: List[ddc.ClusterSet] = [empty] * k
        self._batch: ddc.ClusterSet = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), empty)
        self._pair_d2: Optional[jax.Array] = None
        self._global: Optional[ddc.ClusterSet] = None
        self._maps: Optional[jax.Array] = None
        # Hierarchical aggregation (DESIGN.md §13): with ``agg_degree``
        # set, the flat (K·C)² cache above stays None and the tree owns
        # one small per-node cache per D children instead.
        self._hier: Optional[hierarchy.AggregatorTree] = None
        if scfg.agg_degree is not None:
            self._hier = hierarchy.AggregatorTree(
                k, scfg.agg_degree, self.cfg, meter=meter)
        self.refreshes = 0
        self.delta_refreshes = 0
        self.query_chunks = 0
        self.query_shards_scanned = 0
        # Failure model (DESIGN.md §11): a bounded write-ahead journal of
        # every ingest/evict decision (riding the host mirrors), a
        # quarantine set of shards whose deltas failed the validation
        # gate or whose lane died, and per-shard epochs fencing duplicate
        # deliveries so the merge is exactly-once.
        self._journal = journal_mod.Journal(k, cap, limit=scfg.journal_limit)
        self._quarantined: dict = {}    # shard -> reason
        self._epoch = [0] * k           # delta generation per shard
        self._merged_epoch = [-1] * k   # last epoch folded into the merge
        self.retries = 0                # delta re-deliveries (monotonic)
        self.quarantine_events = 0      # shards ever quarantined (monotonic)
        self.fenced_deltas = 0          # duplicates the epoch fence dropped
        self.phase1_counts = dict.fromkeys(PHASE1_COUNTERS, 0)
        self.degraded_queries = 0       # queries routed around quarantine
        self.last_query_degraded = False
        self._route_degraded = False
        # Snapshot publish/swap (DESIGN.md §12): the last published read
        # view and its monotonic version counter.  Cut eagerly at the end
        # of every refresh (and on restore), NEVER invalidated by
        # ingest/evict — a held snapshot is stale but consistent.
        self._snapshot: Optional[qt.Snapshot] = None
        self._snapshot_version = 0
        # Cluster tracking (DESIGN.md §14): a pure fold over the merged
        # generations, observed at refresh (post-gate only, so faulted
        # and fault-free runs fold identical inputs).
        self._tracker: Optional[tracking_mod.ClusterTracker] = None
        self._track_snapshot: Optional[tracking_mod.TrackSnapshot] = None
        if scfg.track:
            self._tracker = tracking_mod.ClusterTracker(
                self.cfg, history=scfg.track_history,
                min_overlap=scfg.match_min_overlap)

    # -- data-plane hooks ---------------------------------------------------

    def _append_chunk(self, shard: int, chunk: np.ndarray,
                      idx: np.ndarray, nb: int) -> None:
        raise NotImplementedError

    def _kill_device(self, shard: int, kill: np.ndarray) -> None:
        raise NotImplementedError

    def _restore_lane(self, shard: int, pts: np.ndarray,
                      live: np.ndarray) -> None:
        """Overwrite one shard's device buffers wholesale (the recovery
        upload: journal-replayed points + live mask)."""
        raise NotImplementedError

    def _lose_lane(self, shard: int) -> None:
        """Model a dead lane: its device buffers are gone (zeroed), only
        the host mirrors + journal survive."""
        cap = self.scfg.capacity
        self._restore_lane(shard, np.zeros((cap, 2), np.float32),
                           np.zeros((cap,), bool))
        self._invalidate_reads()

    def _invalidate_reads(self) -> None:
        """Called whenever a write/evict changes the live point set."""

    # -- write path ---------------------------------------------------------

    def _check_shard(self, shard: int) -> int:
        if not 0 <= shard < self.scfg.shards:
            raise ValueError(
                f"shard {shard} out of range [0, {self.scfg.shards}) for "
                f"this {self.scfg.shards}-shard service")
        return shard

    def ingest(self, shard: int, points: np.ndarray,
               t: float | np.ndarray | None = None) -> None:
        """Append ``points`` (n, 2) to ``shard``'s buffer, evicting the
        oldest live points if the buffer would overflow.

        ``t`` stamps the batch for TTL eviction (``evict_older_than``):
        a scalar (whole batch) or an (n,) array (per point).  Default:
        the global ingest sequence number, so count-based and time-based
        eviction coincide when the caller never supplies timestamps.
        """
        self._check_shard(shard)
        cap, bmax = self.scfg.capacity, self.scfg.max_batch
        pts = np.asarray(points, np.float32).reshape(-1, 2)
        n = len(pts)
        with obs.span("ddc.ingest", shard=shard, n=n):
            if t is None:
                ts = np.arange(self._next_seq, self._next_seq + n, dtype=np.float64)
            else:
                ts = np.broadcast_to(np.asarray(t, np.float64), (n,))
            for off in range(0, n, bmax):
                chunk = pts[off:off + bmax]
                nb = len(chunk)
                idx = self._write_slots(shard, nb)
                pad_idx = idx
                if nb < bmax:
                    chunk = np.pad(chunk, ((0, bmax - nb), (0, 0)))
                    pad_idx = np.pad(idx, (0, bmax - nb))
                seqs = np.arange(self._next_seq + off, self._next_seq + off + nb)
                # Write-ahead: journal the decision before the device write,
                # so a lane lost mid-append is still recoverable.
                self._journal.record_ingest(shard, idx, chunk[:nb],
                                            ts[off:off + nb], seqs)
                if shard not in self._quarantined:
                    self._append_chunk(shard, chunk, pad_idx, nb)
                self._live[shard][idx] = True
                self._hpts[shard][idx] = chunk[:nb]
                self._ts[shard][idx] = ts[off:off + nb]
                self._seq[shard][idx] = seqs
                self._head[shard] = int(idx[-1] + 1) % cap
                self._count[shard] = int(self._live[shard].sum())
            if self._journal.needs_compaction(shard):
                self._journal.compact(shard, self._hpts[shard],
                                      self._live[shard], self._ts[shard],
                                      self._seq[shard])
            self._next_seq += n
            if n and shard not in self._quarantined:
                self._dirty.add(shard)
            if n:
                self._bbox[shard] = None
                self._invalidate_reads()

    def _write_slots(self, shard: int, nb: int) -> np.ndarray:
        """Pick the ``nb`` slots the next append chunk writes: dead slots
        in ring order from the head first (so TTL holes are refilled
        before anything live is touched), then — only when the buffer is
        genuinely full — the oldest live points by ingest sequence.  In a
        hole-free ring this reproduces the classic ring-buffer layout
        exactly: the window [head, head+nb) while there is room, the
        oldest window once it wraps."""
        cap = self.scfg.capacity
        live = self._live[shard]
        order = (self._head[shard] + np.arange(cap)) % cap
        dead = order[~live[order]]
        take = dead[:nb]
        if len(take) < nb:
            live_idx = np.nonzero(live)[0]
            oldest = live_idx[np.argsort(self._seq[shard][live_idx],
                                         kind="stable")]
            take = np.concatenate([take, oldest[:nb - len(take)]])
        return take.astype(np.int64)

    def _apply_kill(self, shard: int, kill: np.ndarray) -> int:
        """Clear the live bits marked in ``kill`` (cap,) bool on device
        and in the host mirrors.  Returns the number evicted."""
        self._check_shard(shard)
        n = int(kill.sum())
        if n == 0:
            return 0
        self._journal.record_kill(shard, kill)
        if shard not in self._quarantined:
            self._kill_device(shard, kill)
            self._dirty.add(shard)
        self._live[shard][kill] = False
        self._count[shard] = int(self._live[shard].sum())
        if self._journal.needs_compaction(shard):
            self._journal.compact(shard, self._hpts[shard],
                                  self._live[shard], self._ts[shard],
                                  self._seq[shard])
        self._bbox[shard] = None
        self._invalidate_reads()
        return n

    def evict_oldest(self, shard: int, n: int) -> int:
        """Evict the ``n`` oldest live points from ``shard`` (by ingest
        sequence).  Returns the number actually evicted."""
        self._check_shard(shard)
        live_idx = np.nonzero(self._live[shard])[0]
        if n <= 0 or len(live_idx) == 0:
            return 0
        order = np.argsort(self._seq[shard][live_idx], kind="stable")
        kill = np.zeros((self.scfg.capacity,), bool)
        kill[live_idx[order[:n]]] = True
        return self._apply_kill(shard, kill)

    def evict_older_than(self, shard: int, t: float) -> int:
        """TTL / windowed eviction: evict every live point on ``shard``
        whose ingest timestamp is < ``t``.  Returns the eviction count.
        The ring layout is untouched (holes are legal: liveness is a
        mask, and the append wrap overwrites dead slots for free)."""
        self._check_shard(shard)
        return self._apply_kill(
            shard, self._live[shard] & (self._ts[shard] < t))

    def clear(self, shard: int) -> int:
        """Evict every live point from ``shard``."""
        self._check_shard(shard)
        return self._apply_kill(shard, self._live[shard].copy())

    def window_ts(self) -> Tuple[Optional[float], Optional[float]]:
        """(oldest, newest) live ingest timestamps across all shards,
        from the host timestamp mirrors — the observable window age for
        TTL/sliding-window deployments.  (None, None) when no point is
        live, distinguishing "empty" from a genuine t=0 stamp."""
        lo: Optional[float] = None
        hi: Optional[float] = None
        for s in range(self.scfg.shards):
            live = self._live[s]
            if not live.any():
                continue
            ts = self._ts[s][live]
            tmin, tmax = float(ts.min()), float(ts.max())
            lo = tmin if lo is None else min(lo, tmin)
            hi = tmax if hi is None else max(hi, tmax)
        return lo, hi

    # -- query routing ------------------------------------------------------

    def shard_bbox(self, shard: int) -> Optional[tuple]:
        """(x0, y0, x1, y1) over ``shard``'s live points, or None when
        the shard is empty.  Maintained from the host coordinate mirror
        — updated lazily after any ingest/evict invalidated it — so
        routing never touches the device buffers."""
        self._check_shard(shard)
        box = self._bbox[shard]
        if box is None:
            live = self._live[shard]
            if not live.any():
                box = ()
            else:
                p = self._hpts[shard][live]
                box = (float(p[:, 0].min()), float(p[:, 1].min()),
                       float(p[:, 0].max()), float(p[:, 1].max()))
            self._bbox[shard] = box
        return box or None

    def _route(self, q: np.ndarray) -> np.ndarray:
        """(K,) bool: shards whose ε-dilated live bbox could contain a
        neighbour of ANY row of ``q`` — every other shard provably holds
        no point within ε of any query, so skipping it cannot change a
        single label (exactness).  The shared ``query_tier.bbox_route``
        test (one ``ROUTE_EPS_DILATION`` margin absorbing f32 rounding in
        the distance kernel) is what the snapshot path runs too, so the
        two paths can never route a boundary query differently; counters
        feed ``stats()``.
        """
        k = self.scfg.shards
        scan = qt.bbox_route(
            tuple(self.shard_bbox(s) for s in range(k)), q, self.cfg.eps)
        # Quarantined shards are routed around: the answer is degraded
        # (their points can't label a query until recovery), flagged via
        # ``_route_degraded`` — but healthy shards keep serving.  The
        # bbox test above ran on the *logical* mirrors, so the flag is
        # raised exactly when a quarantined shard could have mattered.
        self._route_degraded = False
        if self._quarantined:
            qmask = np.zeros((k,), bool)
            qmask[list(self._quarantined)] = True
            self._route_degraded = bool((scan & qmask).any())
            scan &= ~qmask
        self.query_chunks += 1
        self.query_shards_scanned += int(scan.sum())
        return scan

    # -- aggregator (delta merge + metering) --------------------------------

    def _merge_and_meter(self, dirty: list, mode: str,
                         up_bytes: int | None = None) -> None:
        """Fold the aggregator mirror into the global state and account
        the up-leg of the exchange: a delta refresh ships |dirty|
        ClusterSets, a full re-merge ships all K.  With ``up_bytes=None``
        (the host-driven engine) the counters are the static model; the
        ``dist`` data plane passes the bytes it MEASURED on its actual
        device→aggregator fetches, so the model-vs-real equality the
        bench asserts is an observation, not a restatement (DESIGN.md
        §10).  Callers meter the map-rows down-leg via
        ``_meter_maps_down`` once the maps exist."""
        cfg = self.cfg
        k, c = self.scfg.shards, cfg.max_clusters
        bbytes = cfg.buffer_bytes()
        exclude = self._exclude_mask()
        if self._hier is not None:
            # Hierarchical aggregation (DESIGN.md §13): shard payloads go
            # to their leaf aggregators; the tree meters its own internal
            # summary/map edges and folds, so only the shard→leaf up-leg
            # is accounted here (model or measured, same as flat).  The
            # flat (K·C)² cache stays None by construction.
            delta = mode == "delta" and self._hier.ready
            self._global, self._maps = self._hier.refresh(
                self._batch, dirty if delta else None, exclude)
            if self.meter is not None:
                if up_bytes is not None:
                    self.meter.add_collective(1, up_bytes)
                else:
                    self.meter.add_collective(
                        len(dirty) if delta else k, bbytes)
            if delta:
                self.delta_refreshes += 1
            return
        if mode == "delta" and self._pair_d2 is not None:
            self._global, self._maps, self._pair_d2 = ddc.merge_delta(
                self._batch, self._pair_d2, dirty, cfg, exclude)
            if self.meter is not None:
                if up_bytes is None:
                    self.meter.add_collective(len(dirty), bbytes)
                else:
                    self.meter.add_collective(1, up_bytes)
            self.delta_refreshes += 1
        else:
            # Full rebuild goes through the same difference-form build
            # (not the Pallas kernel): the cached matrix must stay
            # bit-compatible with the delta patches on every backend —
            # see ddc.contour_pair_d2_exact.
            self._global, self._maps, self._pair_d2 = ddc.merge_delta(
                self._batch, None, None, cfg, exclude)
            if self.meter is not None:
                self.meter.add_collective(
                    *((k, bbytes) if up_bytes is None else (1, up_bytes)))
        if self.meter is not None:
            self.meter.add_merge(k, c)

    def _meter_maps_down(self, nbytes: int | None = None) -> None:
        """Account the down-leg: each shard's (C,) slot-map row.  The
        model counts K·C·4; the dist engine passes the measured size of
        the maps array it actually pushes."""
        if self.meter is not None:
            if nbytes is None:
                self.meter.add_collective(
                    self.scfg.shards, self.cfg.max_clusters * 4)
            else:
                self.meter.add_collective(1, nbytes)

    # -- delta exchange: fault seam, validation gate, retries, fencing ------

    def _exclude_mask(self):
        """(K,) bool quarantine mask for ``merge_delta``/``merge_from_d2``
        (None when every shard is healthy — the identical fast path)."""
        if not self._quarantined:
            return None
        mask = np.zeros((self.scfg.shards,), bool)
        mask[list(self._quarantined)] = True
        return jnp.asarray(mask)

    def _quarantine(self, shard: int, reason: str) -> None:
        """Fence ``shard`` out of merges and query routing.  Its cached
        pair-d2 rows and aggregator mirror stay untouched, so rejoining
        is one ordinary delta patch — that is the bit-exact-recovery
        guarantee."""
        if shard not in self._quarantined:
            self._quarantined[shard] = reason
            self.quarantine_events += 1
        self._dirty.discard(shard)
        self._invalidate_reads()

    @property
    def quarantined(self) -> dict:
        """shard -> reason for every currently quarantined shard."""
        return dict(self._quarantined)

    def _fault_delta(self, shard: int, attempt: int,
                     payload: dict) -> Tuple[dict, bool]:
        """The fault-injection seam on the delta-exchange path.  Consults
        the plan once per delivery attempt; returns the (possibly
        mangled) payload plus a duplicate-delivery flag, or raises
        ``DeltaDropped`` / ``LaneKilled``."""
        if self.faults is None:
            return payload, False
        ev = self.faults.on_delta(shard, attempt)
        if ev is None:
            return payload, False
        if ev.kind in ("drop", "delay"):
            raise faults_mod.DeltaDropped(
                f"shard {shard} delta lost (attempt {attempt})")
        if ev.kind == "kill":
            raise faults_mod.LaneKilled(f"shard {shard} lane died")
        if ev.kind == "dup":
            return payload, True
        return self.faults.mangle(ev.kind, payload), False

    def _gate_and_stage(self, shard: int, payload: dict, epoch: int,
                        cs=None) -> bool:
        """Epoch fence + validation gate in front of the aggregator
        mirror.  A duplicate (epoch already merged) is discarded —
        exactly-once; a corrupt payload raises ``DeltaValidationError``
        BEFORE any mirror or cached pair-d2 state is touched.  ``cs`` is
        the producer's canonical device ClusterSet for this payload, if
        it still has one (dropped when the wire copy was mangled); it
        preserves object identity for the cached empty-shard ClusterSet.
        Returns True iff the delta was staged."""
        if epoch <= self._merged_epoch[shard]:
            self.fenced_deltas += 1
            return False
        faults_mod.validate_delta(payload, self.cfg)
        if cs is None:
            cs = _cs_from_host(payload)
        self._local[shard] = cs
        self._batch = _set_row(self._batch, cs, shard)
        self._merged_epoch[shard] = epoch
        return True

    def _exchange_deltas(self, dirty: list, produce) -> list:
        """Drive one refresh's delta exchange: per-shard delivery with
        retry/backoff (``max_retries``/``retry_backoff``), the fault
        seam, the validation gate, and epoch fencing.  ``produce(shard,
        attempt)`` yields ``(payload, cs)`` — the shard's host-side wire
        payload plus its canonical device ClusterSet when the producer
        has one (re-invoked on retry: the lane re-sends).  Shards whose
        deltas cannot be delivered or fail the gate are quarantined; the
        rest are staged into the aggregator mirror.  Returns the staged
        shard list."""
        staged: list = []
        pending = list(dirty)
        for i in pending:
            self._epoch[i] += 1      # one delta generation per refresh
        attempt = 0
        while pending:
            if attempt > 0:
                self.retries += len(pending)
                if self.scfg.retry_backoff > 0:
                    time.sleep(self.scfg.retry_backoff * 2 ** (attempt - 1))
            still: list = []
            for i in pending:
                epoch = self._epoch[i]
                try:
                    sent, cs = produce(i, attempt)
                    payload, dup = self._fault_delta(i, attempt, sent)
                    if payload is not sent:
                        cs = None    # mangled in flight: trust the wire
                    if self._gate_and_stage(i, payload, epoch, cs):
                        staged.append(i)
                    if dup:
                        # late duplicate of the delta just merged: the
                        # fence must discard it (exactly-once)
                        self._gate_and_stage(i, payload, epoch, cs)
                except faults_mod.DeltaDropped:
                    still.append(i)
                except faults_mod.LaneKilled:
                    self._lose_lane(i)
                    self._quarantine(i, "lane killed mid-refresh")
                except faults_mod.DeltaValidationError as e:
                    self._quarantine(i, f"delta rejected: {e}")
            if still and attempt >= self.scfg.max_retries:
                for i in still:
                    self._quarantine(
                        i, f"delta dropped ({attempt + 1} attempts)")
                break
            pending = still
            attempt += 1
        return staged

    # -- recovery ------------------------------------------------------------

    def recover(self, shard: int) -> bool:
        """Rejoin a quarantined shard: replay the write-ahead journal
        into the ring-buffer state the lane should hold, upload it, and
        mark the shard dirty so the next refresh re-runs phase 1 and
        patches its pair-d2 rows.  Post-recovery state is bit-exact vs
        an uninterrupted run (DESIGN.md §11).  Returns True if the shard
        was quarantined (and is now rejoined)."""
        self._check_shard(shard)
        if shard not in self._quarantined:
            return False
        pts, live, ts, seq = self._journal.replay(shard)
        # The journal rides the host mirrors: replay must land exactly
        # on them, or the log itself is damaged.
        if not (np.array_equal(pts, self._hpts[shard])
                and np.array_equal(live, self._live[shard])
                and np.array_equal(ts, self._ts[shard])
                and np.array_equal(seq, self._seq[shard])):
            raise faults_mod.RecoveryError(
                f"journal replay for shard {shard} diverged from the "
                f"host mirrors; refusing to rejoin")
        self._restore_lane(shard, pts, live)
        del self._quarantined[shard]
        self._dirty.add(shard)
        self._bbox[shard] = None
        self._invalidate_reads()
        return True

    def recover_all(self) -> list:
        """Rejoin every quarantined shard; returns the recovered list."""
        return [s for s in sorted(self._quarantined) if self.recover(s)]

    def refresh(self, mode: str | None = None, force: bool = False,
                track: bool | None = None):
        """Re-cluster dirty shards and fold them into the global state.

        ``mode`` overrides the configured merge mode for this call;
        ``force`` recomputes even with no dirty shards (the full-remerge
        baseline the benchmark times); ``track`` is the per-call
        tracking override (``_track_update``).  Returns the global
        ClusterSet.  The data plane runs phase 1 and the delta exchange
        (``_refresh_shards``) and relabels its points against the new
        slot maps (``_relabel``); the merge and the publish are shared.
        """
        mode = mode or self.scfg.merge_mode
        dirty = sorted(self._dirty - self._quarantined.keys())
        if not dirty and self._global is not None and not force:
            return self._global
        with obs.span("ddc.refresh", dirty=len(dirty), mode=mode):
            staged, up_bytes = self._refresh_shards(dirty, mode)
            # Ends in the publish's host read of the merged set, so the
            # span holds the merge's device work.
            with obs.span("ddc.aggregate", mode=mode, staged=len(staged)):
                self._merge_and_meter(staged, mode, up_bytes)
                self._relabel()
                self._dirty -= set(staged)
                self._track_update(track)
                self.refreshes += 1
                self._publish_snapshot()
        return self._global

    def _refresh_shards(self, dirty: list, mode: str
                        ) -> Tuple[list, Optional[int]]:
        """Data-plane hook: phase 1 on the ``dirty`` shards and their
        delta exchange (``_exchange_deltas``).  Returns (staged shards,
        measured up-leg bytes or None for the metered model)."""
        raise NotImplementedError

    def _relabel(self) -> None:
        """Data-plane hook: send the slot maps down (metered) and
        recompute the global labels of every shard's points."""
        raise NotImplementedError

    # -- cluster tracking (DESIGN.md §14) -----------------------------------

    @property
    def tracker(self) -> Optional[tracking_mod.ClusterTracker]:
        return self._tracker

    def track_snapshot(self) -> Optional[tracking_mod.TrackSnapshot]:
        """The ``TrackSnapshot`` cut alongside the last published read
        view — same version, so labels+tracks reads are consistent.
        None before the first refresh or with tracking disabled."""
        return self._track_snapshot

    def _track_update(self, track: bool | None) -> None:
        """Fold the freshly merged generation into the tracker.

        ``track=None`` (the default) folds iff tracking is enabled and
        no shard is quarantined: the tracker observes only *post-gate*
        complete generations, so a faulted run and its fault-free twin
        fold identical inputs and their histories stay bit-identical
        (the §11 chaos contract extended to tracking).  ``track=False``
        skips the fold for this refresh; ``track=True`` forces it."""
        if self._tracker is None or self._global is None:
            return
        if track is None:
            track = not self._quarantined
        if not track:
            return
        self._tracker.update(self._batch, self._maps, self._global)

    # -- snapshot publish/swap (DESIGN.md §12) ------------------------------

    def _read_view(self):
        """Data-plane hook for snapshot publish: (pts (K, cap, 2), mask
        (K, cap), glabels (K, cap)) device arrays that are safe to hold
        indefinitely — copies of (never aliases into) the donated ring
        buffers."""
        raise NotImplementedError

    def _publish_snapshot(self) -> "qt.Snapshot":
        """Cut and swap in a new immutable read view of the CURRENT
        engine state.  Called at the end of every refresh (and restore),
        so every published version corresponds to one consistent
        (buffers, labels, bboxes, quarantine) observation — a concurrent
        reader sees version V in full or V+1 in full, never a mix."""
        pts, mask, glab = self._read_view()
        k = self.scfg.shards
        self._snapshot_version += 1
        self._snapshot = qt.Snapshot(
            version=self._snapshot_version,
            epoch=self.refreshes,
            published_at=time.monotonic(),
            eps=float(self.cfg.eps),
            pts=pts, mask=mask, glabels=glab,
            bboxes=tuple(self.shard_bbox(s) for s in range(k)),
            quarantined=frozenset(self._quarantined),
            n_live=self.n_live(),
            n_clusters=int(np.asarray(self._global.valid).sum())
            if self._global is not None else 0,
        )
        if self._tracker is not None:
            # Same version as the labels snapshot above: a reader pairing
            # the two sees one consistent generation.
            self._track_snapshot = self._tracker.snapshot(
                version=self._snapshot_version, epoch=self.refreshes)
        return self._snapshot

    def snapshot(self) -> Optional["qt.Snapshot"]:
        """The last published read view (None before the first refresh)."""
        return self._snapshot

    def read_snapshot(self) -> Optional["qt.Snapshot"]:
        """Freshness-seeking read view: fold pending writes (refresh if
        dirty), then return the published snapshot.  None only for the
        empty-service short-circuit (nothing ingested, nothing merged)."""
        if self._global is None and self.n_live() == 0:
            return None
        if self._dirty or self._global is None:
            self.refresh()
        if self._snapshot is None:
            self._publish_snapshot()
        return self._snapshot

    # -- unified read path (both data planes) -------------------------------

    def _query_sync(self, q: np.ndarray):
        """Engine hook: label ``q`` against the current refreshed state.
        Returns (labels (n,) int32, degraded, scanned-shard set)."""
        raise NotImplementedError

    def query(self, points: np.ndarray, return_stale: bool = False,
              legacy: bool = False):
        """Global cluster id for each query point: the label of the
        nearest clustered live point within ``eps`` (DBSCAN's border
        rule against the frozen clustering), else -1.

        Returns a ``QueryResult`` — labels plus the snapshot ``version``
        that answered, the ``degraded`` flag (a quarantined shard could
        have mattered), the routed ``scanned_shards``, and latency.  The
        result duck-types as its labels array, and ``legacy=True`` returns
        the bare ndarray outright (deprecation shim for pre-redesign
        callers); ``return_stale=True`` keeps the old ``(labels, stale)``
        tuple shape with a ``QueryResult`` in the first slot.

        Each chunk is routed to the shards whose ε-dilated bbox could
        contain a neighbour (``_route``); a chunk that reaches no shard
        short-circuits to noise without running a kernel, and a service
        with no live points and no global state yet short-circuits
        entirely (version 0).  Quarantined shards are routed around, so
        healthy shards keep answering during a fault — surfaced via
        ``QueryResult.degraded`` (and the legacy ``last_query_degraded``
        flag + ``degraded_queries`` counter).
        """
        t0 = time.monotonic()
        q = np.asarray(points, np.float32).reshape(-1, 2)
        self.last_query_degraded = False
        if self._global is None and self.n_live() == 0:
            res = qt.QueryResult(
                np.full((len(q),), -1, np.int32), version=0,
                latency_ms=(time.monotonic() - t0) * 1e3)
            return self._query_return(res, return_stale, legacy)
        if self._dirty or self._global is None:
            self.refresh()
        out, degraded, scanned = self._query_sync(q)
        self.last_query_degraded = degraded
        if degraded:
            self.degraded_queries += 1
        res = qt.QueryResult(
            out, version=self._snapshot_version, degraded=degraded,
            scanned_shards=tuple(sorted(scanned)),
            latency_ms=(time.monotonic() - t0) * 1e3)
        return self._query_return(res, return_stale, legacy)

    @staticmethod
    def _query_return(res: "qt.QueryResult", return_stale: bool,
                      legacy: bool):
        out = res.labels if legacy else res
        return (out, res.degraded) if return_stale else out

    def service_stats(self, tier: "qt.QueryTier | None" = None
                      ) -> "qt.ServiceStats":
        """The typed stats contract (DESIGN.md §12): monotonic counters,
        point-in-time gauges, and the comm meter snapshot.  ``tier``
        folds a ``QueryTier``'s serving counters in; the legacy
        ``stats()`` dict is derived from this via ``as_dict()``."""
        tc = tier.counters() if tier is not None else {}
        counters = qt.ServiceCounters(
            refreshes=self.refreshes,
            delta_refreshes=self.delta_refreshes,
            snapshots_published=self._snapshot_version,
            query_chunks=self.query_chunks,
            query_shards_scanned=self.query_shards_scanned,
            queries_served=tc.get("queries_served", 0),
            query_launches=tc.get("query_launches", 0),
            coalesced_requests=tc.get("coalesced_requests", 0),
            query_rows=tc.get("query_rows", 0),
            deadline_misses=tc.get("deadline_misses", 0),
            degraded_queries=self.degraded_queries
            + tc.get("degraded_queries", 0),
            retries=self.retries,
            quarantine_events=self.quarantine_events,
            fenced_deltas=self.fenced_deltas,
            **self.phase1_counts,
            journal_entries=self._journal.entries_total,
        )
        oldest_ts, newest_ts = self.window_ts()
        gauges = qt.ServiceGauges(
            shards=self.scfg.shards,
            capacity=self.scfg.capacity,
            n_live=self.n_live(),
            oldest_ts=oldest_ts,
            newest_ts=newest_ts,
            n_clusters=int(np.asarray(self._global.valid).sum())
            if self._global is not None else 0,
            snapshot_version=self._snapshot_version,
            snapshot_epoch=self._snapshot.epoch
            if self._snapshot is not None else 0,
            quarantined_now=tuple(sorted(self._quarantined)),
            queue_pending=tier.pending if tier is not None else 0,
            jit_cache_entries=qt.snapshot_query_cache_entries(),
        )
        comm = self.meter.snapshot() if self.meter is not None else {}
        return qt.ServiceStats(backend=self.flavor, counters=counters,
                               gauges=gauges, comm=comm)

    def remerge_full(self):
        """Recompute the global state from scratch (the baseline the
        delta path is measured against).  Exactness contract: the result
        is bit-identical to the incrementally maintained state."""
        return self.refresh(mode="full", force=True)

    # -- snapshot helpers (shared by both data planes) ----------------------

    def _mirror_arrays(self) -> dict:
        """The control-plane mirrors + aggregator ClusterSet cache, as
        the numpy dict both engines' ``state_dict`` builds on."""
        arrays = {
            "live": np.stack(self._live),
            "ts": np.stack(self._ts),
            "seq": np.stack(self._seq),
            # The authoritative host point mirror.  Healthy lanes hold
            # the same bits on device, but a quarantined lane's device
            # buffer is zeroed — the mirror (not "pts") is what journal
            # replay must land on, so it is serialised in its own right.
            "hpts": np.stack(self._hpts),
            "batch_contours": np.asarray(self._batch.contours),
            "batch_counts": np.asarray(self._batch.counts),
            "batch_sizes": np.asarray(self._batch.sizes),
            "batch_valid": np.asarray(self._batch.valid),
            "batch_overflow": np.asarray(self._batch.overflow),
        }
        if self._pair_d2 is not None:
            arrays["pair_d2"] = np.asarray(self._pair_d2)
        if self._tracker is not None:
            arrays.update(self._tracker.state_arrays())
        return arrays

    def _mirror_manifest(self) -> dict:
        return {
            "shards": self.scfg.shards,
            "capacity": self.scfg.capacity,
            "max_batch": self.scfg.max_batch,
            "max_queries": self.scfg.max_queries,
            "merge_mode": self.scfg.merge_mode,
            "agg_degree": self.scfg.agg_degree,
            "head": list(self._head),
            "count": list(self._count),
            "dirty": sorted(self._dirty),
            "next_seq": self._next_seq,
            "refreshes": self.refreshes,
            "delta_refreshes": self.delta_refreshes,
            "query_chunks": self.query_chunks,
            "query_shards_scanned": self.query_shards_scanned,
            "has_global": self._global is not None,
            "max_retries": self.scfg.max_retries,
            "retry_backoff": self.scfg.retry_backoff,
            "journal_limit": self.scfg.journal_limit,
            "epoch": list(self._epoch),
            "merged_epoch": list(self._merged_epoch),
            "quarantined": [[s, r] for s, r in
                            sorted(self._quarantined.items())],
            "retries": self.retries,
            "quarantine_events": self.quarantine_events,
            "fenced_deltas": self.fenced_deltas,
            **self.phase1_counts,
            "degraded_queries": self.degraded_queries,
            "journal_entries": self._journal.entries_total,
            "snapshot_version": self._snapshot_version,
            "track": self.scfg.track,
            "track_history": self.scfg.track_history,
            "match_min_overlap": self.scfg.match_min_overlap,
            "tracker": self._tracker.state_manifest()
            if self._tracker is not None else None,
        }

    def _restore_mirrors(self, arrays: dict, manifest: dict) -> None:
        """Rebuild every host mirror — including the coordinate mirror
        backing the bbox router — from ``state_dict`` output."""
        k = self.scfg.shards
        self._live = [np.asarray(arrays["live"][i], bool) for i in range(k)]
        self._ts = [np.asarray(arrays["ts"][i], np.float64) for i in range(k)]
        self._seq = [np.asarray(arrays["seq"][i], np.int64) for i in range(k)]
        hpts = arrays.get("hpts", arrays["pts"])   # pre-§11 fallback
        self._hpts = [np.asarray(hpts[i], np.float32).copy()
                      for i in range(k)]
        self._bbox = [None] * k
        self._head = [int(h) for h in manifest["head"]]
        self._count = [int(c) for c in manifest["count"]]
        self._next_seq = int(manifest["next_seq"])
        self._dirty = set(int(s) for s in manifest["dirty"])
        self.refreshes = int(manifest["refreshes"])
        self.delta_refreshes = int(manifest["delta_refreshes"])
        self.query_chunks = int(manifest.get("query_chunks", 0))
        self.query_shards_scanned = int(
            manifest.get("query_shards_scanned", 0))
        # Failure-model mirrors (absent in pre-§11 snapshots -> healthy
        # defaults).  The journal is not serialised: its base is re-set
        # to the restored mirrors, so a restored service can still
        # quarantine-and-recover from this point on.
        self._epoch = [int(e) for e in manifest.get("epoch", [0] * k)]
        self._merged_epoch = [int(e) for e in
                              manifest.get("merged_epoch", [-1] * k)]
        self._quarantined = {int(s): str(r)
                             for s, r in manifest.get("quarantined", [])}
        self.retries = int(manifest.get("retries", 0))
        self.quarantine_events = int(manifest.get("quarantine_events", 0))
        self.fenced_deltas = int(manifest.get("fenced_deltas", 0))
        self.phase1_counts = {c: int(manifest.get(c, 0))
                              for c in PHASE1_COUNTERS}
        self.degraded_queries = int(manifest.get("degraded_queries", 0))
        # Version monotonicity survives save/load: the restore publish
        # continues from the saved counter, never rewinds it.
        self._snapshot_version = int(manifest.get("snapshot_version", 0))
        self._journal.entries_total = int(manifest.get("journal_entries", 0))
        for s in range(k):
            self._journal.compact(s, self._hpts[s], self._live[s],
                                  self._ts[s], self._seq[s])
        self._journal.compactions = 0
        # Tracker state (absent in pre-§14 snapshots -> fresh tracker).
        if self._tracker is not None and manifest.get("tracker") is not None:
            self._tracker.load_state(arrays, manifest["tracker"])

    def _restore_batch(self, arrays: dict) -> None:
        """Rebuild the aggregator ClusterSet mirror (and the per-shard
        views) from ``state_dict`` output."""
        k = self.scfg.shards
        self._batch = ddc.ClusterSet(
            contours=jnp.asarray(arrays["batch_contours"], jnp.float32),
            counts=jnp.asarray(arrays["batch_counts"], jnp.int32),
            sizes=jnp.asarray(arrays["batch_sizes"], jnp.int32),
            valid=jnp.asarray(arrays["batch_valid"], bool),
            overflow=jnp.asarray(arrays["batch_overflow"], bool),
        )
        self._local = [jax.tree.map(lambda x, i=i: x[i], self._batch)
                       for i in range(k)]

    def _restore_global(self, arrays: dict, manifest: dict) -> bool:
        """Recompute global set + slot maps after ``_restore_batch``.

        Flat mode replays the saved pair-d2 cache through
        ``merge_from_d2``; hierarchical mode rebuilds every node cache
        from scratch over the restored batch — bit-identical to the
        pre-save tree by the per-node DESIGN §8 argument (delta-patched ≡
        from-scratch), so nothing tree-shaped needs serialising.  Returns
        False when the saved engine had no global state yet (callers skip
        the label rebuild + publish)."""
        if not manifest.get("has_global"):
            return False
        if self._hier is not None:
            self._global, self._maps = self._hier.refresh(
                self._batch, None, self._exclude_mask())
            return True
        if "pair_d2" not in arrays:
            return False
        self._pair_d2 = jnp.asarray(arrays["pair_d2"], jnp.float32)
        self._global, self._maps = ddc.merge_from_d2(
            self._batch, self._pair_d2, self.cfg, self._exclude_mask())
        return True

    # -- introspection ------------------------------------------------------

    def n_live(self) -> int:
        return sum(self._count)

    def _live_buffers(self):
        """Data-plane hook for ``live()``: fetch (pts (K, cap, 2),
        mask (K, cap), glabels (K, cap)) as numpy arrays."""
        raise NotImplementedError

    def live(self) -> Tuple[np.ndarray, list, np.ndarray]:
        """Materialise the live state for host-side checks.

        Returns (points (L, 2), parts, labels (L,)): ``parts[s]`` indexes
        the rows of ``points`` held by shard ``s`` — exactly the explicit
        partition ``ddc.ddc_host`` accepts, so streaming≡batch
        equivalence is checked on identical per-shard memberships.
        """
        if self._dirty or self._global is None:
            self.refresh()
        with obs.span("ddc.live") as attrs:
            pts, mask, glab = self._live_buffers()
            pts_rows, parts, labels = [], [], []
            base = 0
            for s in range(self.scfg.shards):
                msk = mask[s]
                pts_rows.append(pts[s][msk])
                labels.append(glab[s][msk])
                parts.append(np.arange(base, base + int(msk.sum())))
                base += int(msk.sum())
            attrs["n_live"] = base
        return (np.concatenate(pts_rows) if base else np.zeros((0, 2), np.float32),
                parts,
                np.concatenate(labels) if base else np.zeros((0,), np.int32))

    def local_set(self, shard: int) -> ddc.ClusterSet:
        self._check_shard(shard)
        return self._local[shard]

    @property
    def pair_d2(self) -> Optional[jax.Array]:
        """Snapshot (copy) of the cached slot-distance matrix.  The live
        buffer is donated to the next delta refresh, so handing out a
        reference would leave callers holding a deleted array."""
        return None if self._pair_d2 is None else jnp.array(self._pair_d2)

    @property
    def hierarchy(self) -> Optional[hierarchy.AggregatorTree]:
        """The aggregator tree (None in flat mode).  In hierarchical mode
        ``pair_d2`` is None by construction — the per-node caches are the
        cache, reachable here for tests and the chaos sweep."""
        return self._hier

    @property
    def global_set(self) -> Optional[ddc.ClusterSet]:
        return self._global

    def routing_stats(self) -> dict:
        return {
            "query_chunks": self.query_chunks,
            "query_shards_scanned": self.query_shards_scanned,
            "query_shards_possible": self.query_chunks * self.scfg.shards,
        }

    def stats(self) -> dict:
        """Legacy dict view, now DERIVED from the typed ``ServiceStats``
        (``service_stats().as_dict()``) so the two can never drift."""
        return self.service_stats().as_dict()


# ---------------------------------------------------------------------------
# The host-driven service
# ---------------------------------------------------------------------------


class ClusterService(ShardControlPlane):
    """Host-driven streaming DDC engine over K logical shards.

    Write path: ``ingest(shard, points)`` appends into the shard's ring
    buffer (evicting the oldest on overflow) and marks it dirty;
    ``refresh()`` re-clusters dirty shards and delta-merges them into the
    cached global state.  Read path: ``query(points)`` returns global
    cluster ids against the last refreshed state (auto-refreshing if
    writes are pending), scanning only bbox-routed candidate shards.
    All device state is static-shape, so every kernel compiles once per
    (StreamConfig) and is reused for the lifetime of the service.
    """

    flavor = "stream"

    def __init__(self, scfg: StreamConfig, meter: ddc.CommMeter | None = None,
                 faults: faults_mod.FaultPlan | None = None):
        super().__init__(scfg, meter, faults=faults)
        k, cap = scfg.shards, scfg.capacity
        self._pts: List[jax.Array] = [
            jnp.zeros((cap, 2), jnp.float32) for _ in range(k)]
        self._mask: List[jax.Array] = [jnp.zeros((cap,), bool) for _ in range(k)]
        self._dense = jnp.full((k, cap), -1, jnp.int32)
        self._glabels = jnp.full((k, cap), -1, jnp.int32)
        self._stack_cache: dict = {}

    # -- data plane ---------------------------------------------------------

    def _append_chunk(self, shard, chunk, idx, nb) -> None:
        self._pts[shard], self._mask[shard] = _append(
            self._pts[shard], self._mask[shard],
            jnp.asarray(chunk), jnp.asarray(idx), nb)

    def _kill_device(self, shard, kill) -> None:
        self._mask[shard] = _kill_mask(self._mask[shard], jnp.asarray(kill))

    def _restore_lane(self, shard, pts, live) -> None:
        self._pts[shard] = jnp.asarray(pts, jnp.float32)
        self._mask[shard] = jnp.asarray(live, bool)

    def _invalidate_reads(self) -> None:
        self._stack_cache.clear()

    # -- refresh (phase 1 on dirty shards + delta/full merge) --------------

    def _refresh_shards(self, dirty, mode):
        cfg = self.cfg

        def produce(i, attempt):
            with obs.span("ddc.phase1", shard=i, attempt=attempt) as attrs:
                if self._count[i] == 0:
                    # Emptied shard: the cached all-invalid ClusterSet, no
                    # phase-1 work.
                    cs, st = ddc.empty_clusterset(cfg), None
                    dense = jnp.full((self.scfg.capacity,), -1, jnp.int32)
                else:
                    dense, cs, st = ddc.local_phase_stats(
                        self._pts[i], self._mask[i], cfg)
                self._dense = _set_row(self._dense, dense, i)
                payload, st = _cs_to_host(cs, st)
                if st is not None:
                    attrs.update(count_phase1(self.phase1_counts, st))
            return payload, cs

        return self._exchange_deltas(dirty, produce), None

    def _relabel(self) -> None:
        self._meter_maps_down()
        self._glabels = _global_labels(
            self._dense, jnp.stack(self._mask), self._maps)

    # -- read path ---------------------------------------------------------

    def _read_view(self):
        # jnp.stack materialises fresh device arrays (copies), so the
        # snapshot survives the donated in-place ring updates; _glabels
        # is never donated, holding the reference is safe.
        return jnp.stack(self._pts), jnp.stack(self._mask), self._glabels

    def _query_sync(self, q: np.ndarray):
        qmax = self.scfg.max_queries
        degraded = False
        scanned: set = set()
        out = np.empty((len(q),), np.int32)
        for off in range(0, len(q), qmax):
            chunk = q[off:off + qmax]
            nq = len(chunk)
            scan = self._route(chunk)
            degraded |= self._route_degraded
            sel = np.nonzero(scan)[0]
            scanned.update(int(s) for s in sel)
            if len(sel) == 0:
                out[off:off + nq] = -1
                continue
            pts, mask, rows = self._scan_stack(sel)
            glab = jnp.take(self._glabels, rows, axis=0)
            if nq < qmax:
                chunk = np.pad(chunk, ((0, qmax - nq), (0, 0)))
            lab = _query_labels(jnp.asarray(chunk), nq, pts, mask, glab,
                                self.cfg.eps)
            out[off:off + nq] = np.asarray(lab)[:nq]
        return out, degraded, scanned

    def _scan_stack(self, sel: np.ndarray):
        """Stack the scanned shards' buffers, padded to a power-of-two
        width so the query kernel compiles at most log2(K)+1 times.
        Padded rows point at shard 0 with a zeroed mask (inert).  Cached
        per scan set; any ingest/evict invalidates (the buffers are
        replaced by donation)."""
        key = tuple(int(s) for s in sel)
        hit = self._stack_cache.get(key)
        if hit is None:
            spad = 1 << max(0, (len(sel) - 1).bit_length())
            pad = np.concatenate(
                [sel, np.zeros((spad - len(sel),), np.int64)])
            valid = np.arange(spad) < len(sel)
            pts = jnp.stack([self._pts[s] for s in pad])
            mask = jnp.stack([self._mask[s] for s in pad]) \
                & jnp.asarray(valid)[:, None]
            if len(self._stack_cache) > 16:
                self._stack_cache.clear()
            hit = (pts, mask, jnp.asarray(pad))
            self._stack_cache[key] = hit
        return hit

    # -- introspection -----------------------------------------------------

    def _live_buffers(self):
        return (np.stack([np.asarray(p) for p in self._pts]),
                np.stack([np.asarray(m) for m in self._mask]),
                np.asarray(self._glabels))

    # -- snapshot / restore -------------------------------------------------

    def state_dict(self) -> Tuple[dict, dict]:
        """Serialise the FULL engine state as (arrays, manifest).

        Everything downstream of (ring buffers, dense labels, per-shard
        ClusterSets, pair-d2 cache) is a deterministic jitted function of
        those inputs, so the global set / slot maps / global labels are
        *recomputed* on restore (``merge_from_d2`` + ``_global_labels``)
        rather than stored — bit-identical by the DESIGN.md §8 argument,
        and the snapshot stays minimal.  The bbox mirrors rebuild from
        the saved buffers (live slots only), so they are not stored.
        """
        arrays = {
            "pts": np.stack([np.asarray(p) for p in self._pts]),
            "mask": np.stack([np.asarray(m) for m in self._mask]),
            "dense": np.asarray(self._dense),
        } | self._mirror_arrays()
        return arrays, self._mirror_manifest()

    @classmethod
    def from_state(cls, scfg: StreamConfig, arrays: dict, manifest: dict,
                   meter: ddc.CommMeter | None = None,
                   faults: faults_mod.FaultPlan | None = None
                   ) -> "ClusterService":
        """Rebuild a service from ``state_dict`` output.  The restored
        engine resumes bit-identically: same labels, same cached pair-d2
        matrix, same delta/full behaviour on the next refresh — no
        re-cluster of the live points."""
        svc = cls(scfg, meter=meter, faults=faults)
        k = scfg.shards
        svc._pts = [jnp.asarray(arrays["pts"][i], jnp.float32)
                    for i in range(k)]
        svc._mask = [jnp.asarray(arrays["mask"][i], bool) for i in range(k)]
        svc._dense = jnp.asarray(arrays["dense"], jnp.int32)
        svc._restore_mirrors(arrays, manifest)
        svc._restore_batch(arrays)
        if svc._restore_global(arrays, manifest):
            svc._glabels = _global_labels(
                svc._dense, jnp.stack(svc._mask), svc._maps)
            # Restore ends with an eager publish, like refresh does: the
            # version counter continues past the saved one (monotonic).
            svc._publish_snapshot()
        return svc
