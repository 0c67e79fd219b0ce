"""Benchmark-side instrumentation: a compile counter, and timed wrappers
around calls into the program's layers.  None of it changes what the
program computes; each wrapper forwards its call unchanged.

Spans are kept in memory (count, summed seconds, and each duration
where a tail is wanted) and are also written into the profiler's trace
as ``jax.profiler.TraceAnnotation`` named ``bench.<layer>``, so that a
traced run can attribute device idle time to what the host was doing.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class CompileCounter:
    """Counts XLA backend compiles and sums their seconds."""
    count: int = 0
    seconds: float = 0.0

    def _listener(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    @contextlib.contextmanager
    def installed(self):
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self._listener)


@dataclass
class Span:
    calls: int = 0
    seconds: float = 0.0
    durations: List[float] = field(default_factory=list)

    def mean_ms(self) -> Optional[float]:
        return 1e3 * self.seconds / self.calls if self.calls else None


class Spans:
    """Named host spans.  ``keep`` names the spans whose every duration
    is kept (for tails); the others keep count and sum only."""

    def __init__(self, keep=()):
        self.by_name: Dict[str, Span] = {}
        self.keep = set(keep)
        self.recording = True

    def get(self, name: str) -> Span:
        sp = self.by_name.get(name)
        if sp is None:
            sp = self.by_name[name] = Span()
        return sp

    def reset(self) -> None:
        self.by_name.clear()

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                if self.recording:
                    sp = self.get(name)
                    sp.calls += 1
                    sp.seconds += dt
                    if name in self.keep:
                        sp.durations.append(dt)

    def wrap(self, name: str, fn: Callable,
             on_call: Optional[Callable] = None) -> Callable:
        """``fn`` timed as span ``name``; ``on_call(args, kwargs, out)``
        sees each call (for counters that read shapes)."""
        spans = self

        def wrapped(*args, **kwargs):
            with spans.span(name):
                out = fn(*args, **kwargs)
            if on_call is not None and spans.recording:
                on_call(args, kwargs, out)
            return out
        wrapped.__wrapped__ = fn
        return wrapped


@contextlib.contextmanager
def patched(obj, attr: str, make: Callable[[Callable], Callable]):
    """Replace ``obj.attr`` by ``make(original)`` for the block.  A
    missing attribute is an error: the metric that reads the wrapper
    must not fall silent when the program renames what it wraps."""
    orig = getattr(obj, attr)
    setattr(obj, attr, make(orig))
    try:
        yield
    finally:
        setattr(obj, attr, orig)


def peak_memory_bytes(devices) -> Optional[int]:
    """``peak_bytes_in_use`` on the fullest chip, where reported."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
