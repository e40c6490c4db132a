"""The streaming DDC cluster services (cluster_service.py: host-mirror
control plane + host-driven data plane; dist_service.py: the same
control plane over a device-resident shard_map data plane).
faults.py / journal.py are the failure model riding both (DESIGN.md §11): seeded fault
injection, the delta validation gate, and the write-ahead recovery log.
query_tier.py is the high-QPS read path riding on top (DESIGN.md §12):
immutable versioned snapshots published at refresh, coalesced batched
queries with pow2 shape bucketing, and the QueryResult/ServiceStats
API contract.  hierarchy.py is the tree-of-aggregators (DESIGN.md §13)
both engines swap in for the flat aggregator when ``agg_degree`` is set.
tracking.py is the cluster tracking subsystem (DESIGN.md §14): stable
track IDs, lifecycle events, and motion analytics folded over the
refresh generations of either engine.

The re-exports are lazy (PEP 562): importing one submodule does not
drag in the others.
"""

_CLUSTER_EXPORTS = ("ClusterService", "ShardControlPlane", "StreamConfig")
_DIST_EXPORTS = ("DistClusterService",)
_HIERARCHY_EXPORTS = ("AggregatorTree",)
_QUERY_TIER_EXPORTS = ("QueryResult", "QueryTier", "QueueFull", "Snapshot",
                       "ServiceStats", "ServiceCounters", "ServiceGauges",
                       "route_snapshot")
_FAULT_EXPORTS = ("FAULT_KINDS", "FaultEvent", "FaultPlan", "FaultError",
                  "DeltaDropped", "LaneKilled", "DeltaValidationError",
                  "RecoveryError")
_JOURNAL_EXPORTS = ("Journal",)
_TRACKING_EXPORTS = ("ClusterTracker", "TrackSnapshot", "TrackView",
                     "TrackEvent")


def __getattr__(name):
    if name in _CLUSTER_EXPORTS:
        from repro.serve import cluster_service
        return getattr(cluster_service, name)
    if name in _DIST_EXPORTS:
        from repro.serve import dist_service
        return getattr(dist_service, name)
    if name in _HIERARCHY_EXPORTS:
        from repro.serve import hierarchy
        return getattr(hierarchy, name)
    if name in _QUERY_TIER_EXPORTS:
        from repro.serve import query_tier
        return getattr(query_tier, name)
    if name in _FAULT_EXPORTS:
        from repro.serve import faults
        return getattr(faults, name)
    if name in _JOURNAL_EXPORTS:
        from repro.serve import journal
        return getattr(journal, name)
    if name in _TRACKING_EXPORTS:
        from repro.serve import tracking
        return getattr(tracking, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
