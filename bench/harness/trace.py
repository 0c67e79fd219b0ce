"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What a TPU trace holds (read by hand from a TPU v5e trace, kept as
``bench/testdata/tiny_v5e.xplane.pb``):

* one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Modules``
  (one event per executed jitted program, named ``jit_<fn>(<hash>)``)
  and a line ``XLA Ops`` (one event per HLO operation);
* the host plane ``/host:CPU`` with a line ``python`` that carries the
  benchmark's ``jax.profiler.TraceAnnotation`` spans by their names.

Times are nanoseconds on the profiler's clock.  Device and host events
share its origin; on a v5e the two were seen up to about a millisecond
apart, so the attribution of idle gaps to host spans is good to that.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclass
class DeviceTrace:
    """One chip's part of a trace."""
    ops: List[Interval] = field(default_factory=list)
    # module name (hash stripped) -> list of (start_ns, duration_ns)
    modules: Dict[str, List[Tuple[float, float]]] = field(default_factory=dict)
    # op name -> summed duration ns (for the breakdown)
    op_ns: Dict[str, float] = field(default_factory=dict)


@dataclass
class Reduced:
    devices: Dict[int, DeviceTrace]
    spans: Dict[str, List[Interval]]     # host span name -> intervals
    window: Interval                     # the traced window on this clock

    # -- device time --------------------------------------------------- #
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over chips."""
        if not self.devices:
            return 0.0
        tot = sum(union_ns(clip(d.ops, self.window))
                  for d in self.devices.values())
        return tot / len(self.devices) * 1e-9

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.devices:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def module_calls(self, name: str,
                     within: Optional[Interval] = None) -> List[float]:
        """Device seconds of each execution of jitted program ``name``
        (``jit_<fn>``), over all chips, in time order; with ``within``,
        only the executions that start inside that interval."""
        out: List[Tuple[float, float]] = []
        for d in self.devices.values():
            out.extend(d.modules.get(name, []))
        if within is not None:
            out = [(s, d) for s, d in out if within[0] <= s < within[1]]
        return [dur * 1e-9 for _, dur in sorted(out)]

    def module_totals(self) -> Dict[str, Tuple[int, float]]:
        """Per jitted program: executions and device seconds, in the
        window, averaged over chips."""
        out: Dict[str, Tuple[int, float]] = {}
        n = max(len(self.devices), 1)
        for d in self.devices.values():
            for name, runs in d.modules.items():
                k, t = out.get(name, (0, 0.0))
                inw = [dur for s, dur in runs
                       if self.window[0] <= s < self.window[1]]
                out[name] = (k + len(inw), t + sum(inw) * 1e-9 / n)
        return out

    # -- breakdown ------------------------------------------------------ #
    def top_ops(self, k: int = 10) -> List[List]:
        tot: Dict[str, float] = {}
        for d in self.devices.values():
            for name, ns in d.op_ns.items():
                tot[name] = tot.get(name, 0.0) + ns
        n = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n * 1e-9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Device idle time, summed by the innermost benchmark host span
        that covers each stretch (``host`` where none does), for the
        first chip; the ``k`` largest."""
        if not self.devices:
            return []
        dev = self.devices[min(self.devices)]
        gaps = complement(merge(clip(dev.ops, self.window)), self.window)
        spans = sorted((s, e, name) for name, ivs in self.spans.items()
                       for s, e in ivs)
        tot: Dict[str, float] = {}
        for gs, ge in gaps:
            for name, ns in _attribute(gs, ge, spans):
                tot[name] = tot.get(name, 0.0) + ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top]


def _attribute(gs: float, ge: float,
               spans: Sequence[Tuple[float, float, str]]):
    """Split one idle gap by the innermost (latest-starting) span that
    covers each part of it."""
    cuts = {gs, ge}
    for s, e, _ in spans:
        if e > gs and s < ge:
            cuts.update(x for x in (s, e) if gs < x < ge)
    pts = sorted(cuts)
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        name = "host"
        for s, e, n in spans:
            if s > mid:
                break
            if e > mid:
                name = n           # spans are start-sorted: last = innermost
        yield name, b - a


# ---------------------------------------------------------------------- #
# interval arithmetic
# ---------------------------------------------------------------------- #
def merge(ivs: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(ivs: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merge(ivs))


def clip(ivs: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def complement(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    out, t = [], window[0]
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


# ---------------------------------------------------------------------- #
# reading
# ---------------------------------------------------------------------- #
def module_name(event_name: str) -> str:
    """``jit_serve_step(123)`` -> ``jit_serve_step``."""
    return MODULE_NAME.match(event_name).group(1)


def reduce_xplane(path: str, window_span: str = "bench.window") -> Reduced:
    """Read one ``.xplane.pb``.  The traced window is the host span
    named ``window_span`` where the benchmark recorded one, else the
    extent of all device operations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, DeviceTrace] = {}
    spans: Dict[str, List[Interval]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), DeviceTrace())
            raw_ops: List[Tuple[float, float, str]] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        raw_ops.append((ev.start_ns, ev.duration_ns, ev.name))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        dev.modules.setdefault(module_name(ev.name), []) \
                            .append((ev.start_ns, ev.duration_ns))
            _file_ops(dev, raw_ops)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    win = spans.get(window_span)
    if win:
        window = (min(s for s, _ in win), max(e for _, e in win))
    else:
        allops = [iv for d in devices.values() for iv in d.ops]
        window = (min(s for s, _ in allops), max(e for _, e in allops)) \
            if allops else (0.0, 0.0)
    return Reduced(devices=devices, spans=spans, window=window)


def _file_ops(dev: DeviceTrace, raw_ops) -> None:
    """Record each op's interval, and its time under the name
    ``<module>:<op> <type>`` (the module whose execution contains it)."""
    import bisect
    mods = sorted((s, s + d, name) for name, runs in dev.modules.items()
                  for s, d in runs)
    starts = [m[0] for m in mods]
    for s, d, name in raw_ops:
        dev.ops.append((s, s + d))
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        key = f"{mod}:{op_label(name)}"
        dev.op_ns[key] = dev.op_ns.get(key, 0.0) + d


def op_label(name: str) -> str:
    """``%fusion.12 = bf16[32,8192]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 bf16[32,8192]``: the HLO op and its result type."""
    head, _, rest = name.partition(" = ")
    typ = rest.split(" ", 1)[0]
    typ = "(...)" if typ.startswith("(") else re.sub(r"\{[^}]*\}", "", typ)
    return f"{head.lstrip('%')} {typ}"[:120].strip()


def find_xplane(log_dir: str) -> Optional[str]:
    import glob
    import os
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None
