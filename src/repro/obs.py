"""Spans: where a fit's time goes, recorded by the program itself.

``span(name, **attrs)`` wraps one layer's work at the boundary where the
work happens.  It yields its ``attrs`` dict, so values known only at the
end (a sweep count fetched from the device) can be set inside.  A
finished span holds its ``name``, ``start`` and ``end`` on
``time.perf_counter()``, its ``span_id``, the ``parent_id`` of the span
open around it and the ``trace_id`` of its root: every span under one
root shares the root's id.  Parents come from a ``contextvars`` variable,
so a span opened on another thread starts a trace of its own instead of
nesting into whatever that thread's creator had open.

Every span also enters ``jax.profiler.TraceAnnotation(name)``, which
puts it on the profiler's clock beside the device's operations whenever
a trace is being taken.  There is no switch: with no profiler running a
span costs a few microseconds of host time (the annotation, a context
variable, a ring append), against the milliseconds of the work it wraps.

Finished spans go into one bounded ring (``RING_SIZE`` spans, oldest
dropped first).  ``spans()`` copies it, ``clear()`` empties it, and
``lost_since(t)`` says whether a span that ended at or after ``t`` was
dropped, so a reader of a time window knows when its spans are
incomplete.

The spans of the fit path, and what each holds:

* ``ddc.fit`` -- ``DDC.fit``: the root of one trace per fit (attrs
  ``backend``, ``n``).
* ``ddc.ingest`` -- ``ShardControlPlane.ingest``: host mirrors and the
  append dispatch of one batch (``shard``, ``n``).
* ``ddc.refresh`` -- a refresh that had work to do (``dirty``, ``mode``).
* ``ddc.phase1`` -- one shard's phase 1 in the stream engine, from
  ``local_phase`` to the end of the shard's host copy (``shard``,
  ``attempt``; and, when phase 1 ran, ``sweeps``, ``doubling_steps``,
  ``tile_pairs_active``, ``tile_pairs``, ``dense_fallback``).
* ``ddc.aggregate`` -- the merge, the global labels and the snapshot
  publish, which ends in a host read of the merged set (``mode``,
  ``staged``).
* ``ddc.live`` -- ``ShardControlPlane.live``, the host copy behind
  ``DDC.labels_`` (``n_live``).
* ``ddc.refit`` -- the ``jit`` backend's whole pipeline, run by the
  first read after a write (``DDC.labels_``): padding, placement on the
  mesh, the program and the label concat (``backend``, ``shards``,
  ``cap``); one trace per refit.
* ``ddc.run`` -- its child: from the call of the ``make_ddc_fn``
  program to the end of its one host read of labels and stats.  Per-lane
  lists ``sweeps``, ``doubling_steps``, ``tile_pairs_active``,
  ``tile_pairs``, ``dense_fallback``; ``overflow``, the global set's
  cluster-budget flag; ``truncated``, the local and merged contours cut
  at ``max_verts``.
"""
from __future__ import annotations

import collections
import contextvars
import itertools
import math
import threading
import time
from typing import NamedTuple, Optional

import jax

SPAN_NAMES = ("ddc.fit", "ddc.ingest", "ddc.refresh", "ddc.phase1",
              "ddc.aggregate", "ddc.live", "ddc.refit", "ddc.run")
RING_SIZE = 4096

# (span_id, trace_id) of the innermost open span in this context.
_OPEN: contextvars.ContextVar = contextvars.ContextVar("repro_obs_open",
                                                      default=None)


class Span(NamedTuple):
    name: str
    start: float                # time.perf_counter() seconds
    end: float
    span_id: int
    parent_id: Optional[int]
    trace_id: int
    attrs: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


class _Open:
    """One span while it is open: the context manager ``span`` returns
    (a class, not a generator function, which costs more per call)."""

    __slots__ = ("rec", "name", "attrs", "span_id", "parent_id", "trace_id",
                 "token", "ann", "start")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self) -> dict:
        parent = _OPEN.get()
        self.span_id = next(self.rec._ids)
        if parent is None:
            self.parent_id, self.trace_id = None, self.span_id
        else:
            self.parent_id, self.trace_id = parent
        self.token = _OPEN.set((self.span_id, self.trace_id))
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.start = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self.ann.__exit__(*exc)
        _OPEN.reset(self.token)
        self.rec._push(Span(self.name, self.start, end, self.span_id,
                            self.parent_id, self.trace_id, self.attrs))


class Recorder:
    """A bounded ring of finished spans."""

    def __init__(self, size: int = RING_SIZE):
        self._ring: collections.deque = collections.deque(maxlen=size)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self.dropped = 0                # spans pushed out of the ring
        self._dropped_end = -math.inf   # latest end among them

    def span(self, name: str, **attrs) -> _Open:
        """Context manager over one layer's work; yields ``attrs``."""
        return _Open(self, name, attrs)

    def _push(self, s: Span) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
                self._dropped_end = max(self._dropped_end, self._ring[0].end)
            self._ring.append(s)

    def spans(self) -> list:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0
            self._dropped_end = -math.inf

    def lost_since(self, t: float) -> bool:
        """True when a span that ended at or after ``t`` was dropped."""
        return self._dropped_end >= t


RECORDER = Recorder()
span = RECORDER.span
spans = RECORDER.spans
clear = RECORDER.clear
lost_since = RECORDER.lost_since
