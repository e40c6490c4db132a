"""How a mix drives the system, through the ``repro.ddc`` facade.

A mix's ``loop`` names one function of ``LOOPS``:

* ``batch_fit`` (closed loop): ``DDC.fit`` followed by ``labels_``, back
  to back on the same points.

Each returns a ``Run``: the end-to-end values, the spans, the numbers
compared against the plain reference beside their limits, and, when
traced, the reduced device trace.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time

import numpy as np

from bench import traffic, trace_reduce
from bench.reference import ddc_ref
from bench.spans import CompileCounter, Recorder

SPANS = ("fit", "labels")


@dataclasses.dataclass
class Run:
    values: dict                 # end-to-end metric name -> value
    rec: Recorder
    info: dict                   # counts the per-layer readers divide by
    compared: dict               # name -> {"value": x, "limit": y}
    attempted: int
    failed: int
    trace: dict | None = None
    notes: list = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.compared.values())


def build_model(config: dict):
    from repro.ddc import DDC, DDCConfig

    kw = dict(config["ddc"])
    kw["bounds"] = tuple(kw["bounds"])
    kw.update(backend=config["backend"], shards=int(config["shards"]))
    return DDC(DDCConfig(**kw))


class _Tracer:
    """Profiler trace of the measured window, written under TMPDIR and
    reduced when the window closes."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self._ann = None

    def start(self):
        if self.on:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._ann.__enter__()

    def stop(self):
        if self.on:
            import jax

            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def reduce(self):
        if not self.on:
            return None
        try:
            events = trace_reduce.load_events(trace_reduce.find_trace(self.dir))
            return trace_reduce.reduce(events, span_names=SPANS)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks, default=0))


# -- batch_fit ----------------------------------------------------------------


def run_batch_fit(config: dict, mix: dict, seed: int, seconds: float,
                  trace: bool, t_start: float) -> Run:
    rec = Recorder(trace)
    counter = CompileCounter.install()
    pts = traffic.batch_fit(config, mix, seed)["points"]
    model = build_model(config)
    warm = np.asarray(model.fit(pts).labels_)
    tracer = _Tracer(trace)
    mark = counter.mark()
    tracer.start()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    fits = []
    while True:
        with rec.span("fit"):
            model.fit(pts)
            with rec.span("labels"):
                lab = np.asarray(model.labels_)
        fits.append(lab)
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    tracer.stop()
    lowered, compiled = counter.since(mark)
    reduced = tracer.reduce()
    peak = _peak_bytes(int(config["chips"]))
    values = {"setup_s": setup_s, "fit_s": window_s / len(fits)}

    # Correctness, once the window has closed and the peak is read: every
    # fit's labels, the first against the reference on the same block
    # partition, the others (and the warm-up's) against the first.
    del model
    t_ref = time.perf_counter()
    shards = int(config["shards"])
    parts = np.array_split(np.arange(len(pts)), shards)
    ref = ddc_ref.ddc([pts[p] for p in parts], config["ddc"])
    want = np.concatenate(ref["labels"])
    ref_s = time.perf_counter() - t_ref
    mismatch = ddc_ref.partition_mismatch(fits[0], want) \
        if len(fits[0]) == len(want) else len(want)
    differ = sum(int(not np.array_equal(f, fits[0])) for f in fits[1:]) \
        + int(not np.array_equal(warm, fits[0]))
    compared = {"label_mismatch": {"value": mismatch, "limit": 0},
                "fits_differ": {"value": differ, "limit": 0}}
    d = config["ddc"]
    notes = [f"programs lowered in the window: {lowered}; backend compiles "
             f"in the window: {compiled}",
             f"reference: {ref['n_global']} global clusters, noise share "
             f"{float((want < 0).mean()):.4f}; local clusters "
             f"per shard {ref['local']} (budget {d['max_clusters']}); largest "
             f"local contour {ref['contour_max']} cells (budget {d['max_verts']})",
             f"window {window_s:.3f} s, {len(fits)} fits; reference {ref_s:.3f} s"]
    info = {"window_s": window_s, "fits": len(fits), "compiles": (lowered, compiled),
            "peak_bytes": peak}
    return Run(values=values, rec=rec, info=info, compared=compared,
               attempted=len(fits), failed=0, trace=reduced, notes=notes)


LOOPS = {"batch_fit": run_batch_fit}
