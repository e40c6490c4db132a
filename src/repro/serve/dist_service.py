"""Device-resident streaming DDC data plane (the ``dist`` backend).

``ClusterService`` drives K *logical* shards from the host: every ring
buffer lives on the default device and the phase-2 exchange is a metered
model.  This module keeps the exact same control plane
(``ShardControlPlane``: slot choice, eviction, TTL stamps, bbox routing,
dirty tracking) but pins each shard's data to its own mesh device and
makes the exchange real (DESIGN.md §10):

* **Pinned buffers** — points/mask/dense/glabels are stacked (K, …)
  arrays sharded ``P("shards", …)`` over a K-device host mesh: shard
  ``i``'s rows live on device ``i`` and never leave it.
* **shard_map ingest / eviction / phase 1** — the ring scatter, the
  kill-mask, and dirty-shard ``local_phase`` all run as per-lane bodies
  inside ``shard_map`` over the mesh axis.  The host mirrors still pick
  the slots/victims (a pure function of the call sequence), so the
  per-lane kernels stay single static-shape scatters; a per-lane
  ``lax.cond`` on the dirty flag means clean lanes do no phase-1 work.
* **Delta-ClusterSet exchange** — the ONLY payload that crosses the mesh
  axis per refresh: each dirty lane's fixed-size ClusterSet (contours +
  counts + sizes + valid + overflow, ``DDCConfig.buffer_bytes()`` each)
  moves device→aggregator, and each lane's (C,) slot-map row moves back
  (K·C·4 bytes total).  The aggregator (the control plane's ClusterSet
  mirror + cached pair-d2 matrix) patches only the dirty rows/columns —
  ``ddc.merge_delta``, the same code path as the host-driven engine, so
  the result is bit-identical to it (and to batch ``ddc_host``).  The
  CommMeter counters are therefore *real* axis-crossing bytes here, not
  a model: |dirty|·B + K·C·4 per delta refresh, K·B + K·C·4 for a full
  re-merge (which genuinely re-ships every lane's ClusterSet).
* **Routed queries** — a query chunk is broadcast only conceptually: each
  lane whose ε-dilated bbox could contain a neighbour (host bbox
  mirrors) computes its local (best-d2, label) per query under a
  per-lane ``lax.cond``; skipped lanes return the identity.  The host
  folds lanes in ascending shard order with a strict ``<`` so ties
  resolve exactly like the flat argmin of the host-driven engine.

Phase-1 numerics are bit-identical between the two data planes (the
per-lane ``local_phase`` is the same XLA program as the per-shard jit),
so labels AND the cached pair-d2 matrix match the ``stream`` engine
bit-for-bit — asserted per layout × shard count by
tests/_dist_backend_script.py.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ddc
from repro.launch import mesh as mesh_mod
from repro.parallel import compress
from repro.serve import faults as faults_mod
from repro.serve.cluster_service import (
    ShardControlPlane, StreamConfig, _cs_from_host, _set_row,
)

AXIS = "shards"


def require_devices(shards: int) -> None:
    """The dist data plane pins one shard per device; fail with the fix
    spelled out instead of an opaque mesh error."""
    shortfall = mesh_mod.device_shortfall(shards)
    if shortfall:
        raise ValueError(f"backend='dist' pins one shard per device: "
                         f"shards={shards} {shortfall}")


@functools.lru_cache(maxsize=None)
def _data_plane(mesh, cfg: ddc.DDCConfig, cap: int, bmax: int, qmax: int):
    """Build (once per (mesh, config, shapes)) the jitted shard_map
    kernels of the device data plane.  Every body sees its lane's
    (1, …) block; donation keeps ring updates in place on each device.
    """
    s1, s2, s3 = P(AXIS), P(AXIS, None), P(AXIS, None, None)
    cs_spec = ddc.ClusterSet(
        contours=P(AXIS, None, None, None), counts=s2, sizes=s2,
        valid=s2, overflow=s1)
    empty_cs = ddc.empty_clusterset(cfg)

    def smap(f, in_specs, out_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def lane_append(pts, mask, chunk, idx, nb):
        wvalid = jnp.arange(chunk.shape[1]) < nb[0]
        safe = jnp.where(wvalid, idx[0], cap)        # invalid rows drop
        p = pts[0].at[safe].set(chunk[0], mode="drop")
        m = mask[0].at[safe].set(True, mode="drop")
        return p[None], m[None]

    append = jax.jit(
        smap(lane_append, (s3, s2, s3, s2, s1), (s3, s2)),
        donate_argnums=(0, 1))

    def lane_kill(mask, kill):
        return (mask[0] & ~kill[0])[None]

    kill = jax.jit(smap(lane_kill, (s2, s2), s2), donate_argnums=(0,))

    def lane_restore(pts, mask, npts, nmask, flag):
        # Recovery upload: the flagged lane's buffers are replaced
        # wholesale (journal-replayed state); other lanes untouched.
        p = jnp.where(flag[0], npts[0], pts[0])
        m = jnp.where(flag[0], nmask[0], mask[0])
        return p[None], m[None]

    restore = jax.jit(smap(lane_restore, (s3, s2, s3, s2, s1), (s3, s2)),
                      donate_argnums=(0, 1))

    def lane_refresh(pts, mask, dense, cs, dirty):
        p, m = pts[0], mask[0]
        old = dense[0], jax.tree.map(lambda x: x[0], cs)

        def recompute(_):
            def nonempty(_):
                return ddc.local_phase(p, m, cfg)

            def emptied(_):
                # Emptied shard: the cached all-invalid ClusterSet (the
                # PR 2 empty-shard fix, lane-local edition).
                return jnp.full((cap,), -1, jnp.int32), empty_cs

            return jax.lax.cond(jnp.any(m), nonempty, emptied, None)

        nd, ncs = jax.lax.cond(dirty[0], recompute, lambda _: old, None)
        return nd[None], jax.tree.map(lambda x: x[None], ncs)

    refresh = jax.jit(
        smap(lane_refresh, (s3, s2, s2, cs_spec, s1), (s2, cs_spec)),
        donate_argnums=(2, 3))

    def lane_labels(dense, mask, maps):
        d, m, mp = dense[0], mask[0], maps[0]
        return jnp.where(m & (d >= 0), mp[jnp.clip(d, 0)], -1)[None]

    labels = jax.jit(smap(lane_labels, (s2, s2, s2), s2))

    def lane_query(q, pts, mask, glab, scan):
        def compute(_):
            d2 = jnp.sum((q[:, None, :] - pts[0][None, :, :]) ** 2, axis=-1)
            ok = mask[0] & (glab[0] >= 0)
            d2 = jnp.where(ok[None, :], d2, jnp.float32(1e30))
            j = jnp.argmin(d2, axis=1)
            return d2[jnp.arange(qmax), j], glab[0][j]

        def skipped(_):
            return (jnp.full((qmax,), 1e30, jnp.float32),
                    jnp.full((qmax,), -1, jnp.int32))

        bd, bl = jax.lax.cond(scan[0], compute, skipped, None)
        return bd[None], bl[None]

    query = jax.jit(smap(lane_query, (P(None, None), s3, s2, s2, s1),
                         (s2, s2)))

    return {"append": append, "kill": kill, "restore": restore,
            "refresh": refresh, "labels": labels, "query": query}


class DistClusterService(ShardControlPlane):
    """Streaming DDC engine whose per-shard state is pinned to its own
    mesh device (see module doc).  Same public surface as
    ``ClusterService``; the difference is *where* the data plane runs
    and that the delta-ClusterSet exchange bytes are real transfers.
    """

    flavor = "dist"

    def __init__(self, scfg: StreamConfig, meter: ddc.CommMeter | None = None,
                 faults: faults_mod.FaultPlan | None = None):
        super().__init__(scfg, meter, faults=faults)
        k, cap = scfg.shards, scfg.capacity
        require_devices(k)
        self.mesh = mesh_mod.make_host_mesh(k, axis=AXIS)
        self._fns = _data_plane(self.mesh, self.cfg, cap,
                                scfg.max_batch, scfg.max_queries)
        self._sh1 = NamedSharding(self.mesh, P(AXIS))
        self._sh2 = NamedSharding(self.mesh, P(AXIS, None))
        self._sh3 = NamedSharding(self.mesh, P(AXIS, None, None))
        self._zero_pieces: dict = {}   # (operand, lane) -> zero piece
        self._pts = jax.device_put(np.zeros((k, cap, 2), np.float32), self._sh3)
        self._mask = jax.device_put(np.zeros((k, cap), bool), self._sh2)
        self._dense = jax.device_put(np.full((k, cap), -1, np.int32), self._sh2)
        self._glabels = jax.device_put(
            np.full((k, cap), -1, np.int32), self._sh2)
        # Device-side stacked ClusterSets: lane i's row is its last
        # phase-1 output, resident on device i (clean lanes carry it
        # forward through the per-lane cond without recompute).
        self._batch_dev = jax.tree.map(
            lambda x: jax.device_put(
                np.broadcast_to(np.asarray(x)[None],
                                (k,) + np.asarray(x).shape).copy(),
                NamedSharding(self.mesh,
                              P(AXIS, *([None] * np.asarray(x).ndim)))),
            ddc.empty_clusterset(self.cfg))

    # -- data plane ---------------------------------------------------------

    def _lane_stage(self, name: str, sharding, payload: np.ndarray,
                    shard: int):
        """A (K, …) sharded operand whose lane ``shard`` holds
        ``payload`` and every other lane holds zeros — assembled from
        per-device pieces so ONLY the target lane's payload crosses the
        host→device boundary.  The zero pieces are device-resident and
        cached per (operand, lane); that is safe because none of the
        staged operands are donated by the data-plane kernels."""
        devices = list(self.mesh.devices.flat)
        shape = (len(devices),) + payload.shape
        pieces = []
        for i, dev in enumerate(devices):
            if i == shard:
                pieces.append(jax.device_put(payload[None], dev))
                continue
            key = (name, i)
            zero = self._zero_pieces.get(key)
            if zero is None:
                zero = jax.device_put(
                    np.zeros((1,) + payload.shape, payload.dtype), dev)
                self._zero_pieces[key] = zero
            pieces.append(zero)
        return jax.make_array_from_single_device_arrays(
            shape, sharding, pieces)

    def _append_chunk(self, shard, chunk, idx, nb) -> None:
        self._pts, self._mask = self._fns["append"](
            self._pts, self._mask,
            self._lane_stage("chunk", self._sh3,
                             np.asarray(chunk, np.float32), shard),
            self._lane_stage("idx", self._sh2,
                             np.asarray(idx, np.int32), shard),
            self._lane_stage("nb", self._sh1,
                             np.asarray(nb, np.int32), shard))

    def _kill_device(self, shard, kill) -> None:
        self._mask = self._fns["kill"](
            self._mask,
            self._lane_stage("kill", self._sh2,
                             np.asarray(kill, bool), shard))

    def _restore_lane(self, shard, pts, live) -> None:
        flags = np.zeros((self.scfg.shards,), bool)
        flags[shard] = True
        self._pts, self._mask = self._fns["restore"](
            self._pts, self._mask,
            self._lane_stage("rpts", self._sh3,
                             np.asarray(pts, np.float32), shard),
            self._lane_stage("rmask", self._sh2,
                             np.asarray(live, bool), shard),
            jax.device_put(flags, self._sh1))

    # -- refresh (lane-local phase 1 + delta exchange + merge) --------------

    def _refresh_shards(self, dirty, mode):
        """Re-cluster dirty lanes on their own devices and exchange ONLY
        their delta ClusterSets across the axis; the shared ``refresh``
        then re-closes the cached merge.  Bit-identical to the ``stream``
        engine on the same call sequence (and to a from-scratch
        re-merge), including the tracking fold."""
        k = self.scfg.shards
        if dirty:
            flags = np.zeros((k,), bool)
            flags[dirty] = True
            self._dense, self._batch_dev = self._fns["refresh"](
                self._pts, self._mask, self._dense, self._batch_dev,
                jax.device_put(flags, self._sh1))

        # The axis crossing: dirty lanes' ClusterSets move to the
        # aggregator mirror (a delta refresh ships just those in ONE
        # gathered fetch; a full re-merge genuinely re-ships every
        # lane's).  ``up_bytes`` is measured off the fetched arrays
        # themselves, so the meter reports what actually crossed — the
        # bench's dist-vs-stream byte equality is an observation.  Every
        # payload then passes the control plane's delta exchange (fault
        # seam, validation gate, retry, epoch fence) before it may touch
        # the mirror; a retry is a genuine lane re-send, metered too.
        up_bytes = [0]

        def row_payload(rows, j):
            return {"contours": rows.contours[j], "counts": rows.counts[j],
                    "sizes": rows.sizes[j], "valid": rows.valid[j],
                    "overflow": rows.overflow[j]}

        def refetch(i):
            row = jax.device_get(jax.tree.map(
                lambda x: x[i], self._batch_dev))
            up_bytes[0] += compress.pytree_wire_bytes(row)
            return {"contours": row.contours, "counts": row.counts,
                    "sizes": row.sizes, "valid": row.valid,
                    "overflow": row.overflow}

        # The cached aggregation that makes a delta fetch sufficient is
        # the flat pair-d2 matrix OR the built hierarchy (whose per-node
        # caches play the same role, DESIGN §13).
        delta_ready = (self._hier.ready if self._hier is not None
                       else self._pair_d2 is not None)
        if mode == "delta" and delta_ready:
            payloads = {}
            if dirty:
                rows = jax.device_get(jax.tree.map(
                    lambda x: x[jnp.asarray(dirty)], self._batch_dev))
                up_bytes[0] += compress.pytree_wire_bytes(rows)
                payloads = {i: row_payload(rows, j)
                            for j, i in enumerate(dirty)}

            def produce(i, attempt):
                if attempt == 0 and i in payloads:
                    return payloads[i], None
                return refetch(i), None

            staged = self._exchange_deltas(dirty, produce)
        else:
            # All K lanes re-ship anyway: one bulk fetch; the dirty
            # lanes' payloads still pass the gate, the clean lanes'
            # mirror rows are refreshed in place (bit-identical values).
            fetched = jax.device_get(self._batch_dev)
            up_bytes[0] += compress.pytree_wire_bytes(fetched)
            payloads = {i: row_payload(fetched, i) for i in dirty}

            def produce(i, attempt):
                if attempt == 0:
                    return payloads[i], None
                return refetch(i), None

            staged = self._exchange_deltas(dirty, produce)
            if not self._quarantined and set(staged) == set(dirty):
                self._batch = ddc.ClusterSet(
                    *[jnp.asarray(x) for x in fetched])
                self._local = [jax.tree.map(lambda x, i=i: x[i], self._batch)
                               for i in range(k)]
            else:
                for i in range(k):
                    if i in self._quarantined or i in dirty:
                        continue    # dirty rows went through the gate
                    cs = _cs_from_host(row_payload(fetched, i))
                    self._local[i] = cs
                    self._batch = _set_row(self._batch, cs, i)

        return staged, up_bytes[0]

    def _relabel(self) -> None:
        # Map rows back down, lane-local relabel; again metered from the
        # array actually pushed.
        maps_np = np.asarray(self._maps, np.int32)
        self._meter_maps_down(maps_np.nbytes)
        maps_dev = jax.device_put(maps_np, self._sh2)
        self._glabels = self._fns["labels"](self._dense, self._mask, maps_dev)

    # -- read path ----------------------------------------------------------

    def _read_view(self):
        # The pinned buffers are donated by append/kill/restore, so the
        # snapshot must own genuine copies: fetch to host, re-put on the
        # default device (where the snapshot query kernel runs anyway).
        return (jnp.asarray(np.asarray(self._pts)),
                jnp.asarray(np.asarray(self._mask)),
                jnp.asarray(np.asarray(self._glabels)))

    def _query_sync(self, q: np.ndarray):
        """Lane-local (best-d2, label) per bbox-routed shard, folded on
        the host in ascending shard order with a strict ``<`` so ties
        match the host-driven engine's flat argmin."""
        qmax = self.scfg.max_queries
        k = self.scfg.shards
        eps2 = np.float32(self.cfg.eps) * np.float32(self.cfg.eps)
        degraded = False
        scanned: set = set()
        out = np.empty((len(q),), np.int32)
        for off in range(0, len(q), qmax):
            chunk = q[off:off + qmax]
            nq = len(chunk)
            scan = self._route(chunk)
            degraded |= self._route_degraded
            scanned.update(int(s) for s in np.nonzero(scan)[0])
            if not scan.any():
                out[off:off + nq] = -1
                continue
            if nq < qmax:
                chunk = np.pad(chunk, ((0, qmax - nq), (0, 0)))
            bd, bl = self._fns["query"](
                jnp.asarray(chunk), self._pts, self._mask, self._glabels,
                jax.device_put(scan, self._sh1))
            bd, bl = np.asarray(bd), np.asarray(bl)
            best = np.full((qmax,), 1e30, np.float32)
            lab = np.full((qmax,), -1, np.int32)
            for s in range(k):          # ascending + strict <: ties go to
                upd = bd[s] < best      # the lowest (shard, slot), like
                best = np.where(upd, bd[s], best)   # the flat argmin
                lab = np.where(upd, bl[s], lab)
            out[off:off + nq] = np.where(best <= eps2, lab, -1)[:nq]
        return out, degraded, scanned

    # -- introspection -------------------------------------------------------

    def _live_buffers(self):
        return (np.asarray(self._pts), np.asarray(self._mask),
                np.asarray(self._glabels))

    # -- snapshot / restore --------------------------------------------------

    def state_dict(self) -> Tuple[dict, dict]:
        """Same array/manifest layout as ``ClusterService.state_dict``,
        so snapshots are portable between the two data planes."""
        arrays = {
            "pts": np.asarray(self._pts),
            "mask": np.asarray(self._mask),
            "dense": np.asarray(self._dense),
        } | self._mirror_arrays()
        return arrays, self._mirror_manifest()

    @classmethod
    def from_state(cls, scfg: StreamConfig, arrays: dict, manifest: dict,
                   meter: ddc.CommMeter | None = None,
                   faults: faults_mod.FaultPlan | None = None
                   ) -> "DistClusterService":
        svc = cls(scfg, meter=meter, faults=faults)
        svc._pts = jax.device_put(
            np.asarray(arrays["pts"], np.float32), svc._sh3)
        svc._mask = jax.device_put(np.asarray(arrays["mask"], bool), svc._sh2)
        svc._dense = jax.device_put(
            np.asarray(arrays["dense"], np.int32), svc._sh2)
        svc._restore_mirrors(arrays, manifest)
        svc._restore_batch(arrays)
        svc._batch_dev = jax.tree.map(
            lambda x: jax.device_put(
                np.asarray(x),
                NamedSharding(svc.mesh, P(AXIS, *([None] * (x.ndim - 1))))),
            svc._batch)
        if svc._restore_global(arrays, manifest):
            maps_dev = jax.device_put(
                np.asarray(svc._maps, np.int32), svc._sh2)
            svc._glabels = svc._fns["labels"](svc._dense, svc._mask, maps_dev)
            svc._publish_snapshot()
        return svc
