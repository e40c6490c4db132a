"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What it reads:

* device planes (``/device:TPU:<n>``): the operations on their ``XLA Ops``
  line, each with a start and a duration in nanoseconds;
* host planes: the benchmark's own ``TraceAnnotation`` spans, by name.

What it gives, over the traced window (the host span ``window``):

* ``busy_s``: per device the union of the intervals in which an
  operation ran, averaged over the devices; ``window_s``, the window's
  length (1 - busy / window is the idle share);
* ``groups``: device seconds per named group of operations (kernels,
  collectives), summed over devices and divided by their number, so a
  group's time is per chip;
* ``breakdown``: the ten operations that took the most device time, and
  the ten longest idle gaps of device 0, each named after the innermost
  host span open at the gap's midpoint (``idle`` when none was).

Which operation belongs to which group is data: ``kernel_names.json``
beside this file lists name patterns per group, matched as substrings of
the operation's name.
"""
from __future__ import annotations

import glob
import json
import os
import pathlib

NAMES_FILE = pathlib.Path(__file__).with_name("kernel_names.json")
OPS_LINE = "XLA Ops"
WINDOW = "window"


def find_trace(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def op_name(event_name: str) -> str:
    """A TPU op event is named by its HLO line, ``%neighbor_count.1 =
    s32[...] custom-call(...)``; the op's name is what precedes ``=``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def load_events(path: str) -> dict:
    """{"devices": {plane: [(op name, start_ns, end_ns), ...]},
    "host": [(name, start_ns, end_ns), ...]} from one trace file."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns) for ev in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events)
    return {"devices": devices, "host": host}


def _union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: dict, groups: dict | None = None,
           span_names: tuple = ()) -> dict:
    """The numbers above from ``load_events``' output.  ``groups`` maps a
    group name to its name patterns (default: ``kernel_names.json``);
    ``span_names`` are the host spans gaps may be named after."""
    if groups is None:
        groups = json.loads(NAMES_FILE.read_text())["groups"]
    win = [(s, e) for n, s, e in events["host"] if n == WINDOW]
    if not win:
        raise ValueError("the trace holds no host span named 'window'")
    lo, hi = win[0]
    window_s = (hi - lo) * 1e-9
    # The trace runs from just before the window to just after it, so
    # every device op in it is the window's work.  Ops are not clipped to
    # the host span: the device's clock sits about a millisecond off the
    # host's, which would cut ops at the window's edges.
    devices = {k: v for k, v in events["devices"].items() if v}
    if not devices:
        return {"window_s": window_s, "busy_s": 0.0, "devices": 0,
                "groups": {}, "op_s": {}, "breakdown": None}
    nd = len(devices)
    busy = {k: sum(e - s for s, e in _union([(s, e) for _, s, e in v]))
            for k, v in devices.items()}
    op_s: dict = {}
    for ops in devices.values():
        for n, s, e in ops:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9 / nd
    group_s = {}
    for g, patterns in groups.items():
        group_s[g] = sum(t for n, t in op_s.items()
                         if any(p in n for p in patterns))
    first = sorted(devices)[0]
    merged = _union([(s, e) for _, s, e in devices[first]])
    gaps, prev = [], lo
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    spans = [(n, s, e) for n, s, e in events["host"]
             if n in span_names and n != WINDOW]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) / 2
        open_ = [(n, ss, ee) for n, ss, ee in spans if ss <= mid <= ee]
        name = min(open_, key=lambda x: x[2] - x[1])[0] if open_ else "idle"
        named.append([name, (e - s) * 1e-9])
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) * 1e-9 / nd,
        "devices": nd,
        "groups": group_s,
        "op_s": op_s,
        "breakdown": {"device_ops": [[n, t] for n, t in top],
                      "idle_gaps": named},
    }
