"""The benchmark's own spans, on the host clock, and its compile counter.

A ``fit`` span ends in a host copy of the labels, so it holds the
device work it caused.  In a traced
run every span is also a ``jax.profiler.TraceAnnotation``, which puts it
on the profiler's clock beside the device's operations: the trace
reduction names each idle gap of the device after the span open in it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time


@dataclasses.dataclass
class Span:
    name: str
    start: float          # time.perf_counter() seconds
    end: float
    attrs: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Recorder:
    """Spans of one run.  ``trace`` wraps each span in a profiler
    annotation of the same name."""

    def __init__(self, trace: bool = False):
        self.trace = trace
        self.spans: list[Span] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        if self.trace:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield attrs
        self.spans.append(Span(name, t0, time.perf_counter(), attrs))

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def total(self, name: str) -> float:
        return sum(s.seconds for s in self.named(name))


class CompileCounter:
    """Counts programs lowered and backend compiles in the process, from
    JAX's monitoring events.  A program loaded from the persistent cache
    is lowered but not compiled; either inside the measured window means
    the warm-up missed a shape."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    _installed: "CompileCounter | None" = None

    def __init__(self):
        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0

    @classmethod
    def install(cls) -> "CompileCounter":
        """The process's one counter (listeners cannot be removed, so a
        second install returns the first)."""
        if cls._installed is None:
            from jax import monitoring

            counter = cls()

            def on_duration(event: str, secs: float, **_) -> None:
                if event == cls.LOWER:
                    counter.lowered += 1
                elif event == cls.COMPILE:
                    counter.compiled += 1
                    counter.compile_s += secs

            monitoring.register_event_duration_secs_listener(on_duration)
            cls._installed = counter
        return cls._installed

    def mark(self) -> tuple:
        return (self.lowered, self.compiled)

    def since(self, mark: tuple) -> tuple:
        return (self.lowered - mark[0], self.compiled - mark[1])
