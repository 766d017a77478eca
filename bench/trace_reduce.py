"""From a profiler trace to the per-layer numbers, and the chips' peaks.

Everything here works on plain interval lists so that
``bench/test_bench_trace.py`` can check it on a synthetic trace:

* ``busy_union``: the union of device-op intervals, so that ops that
  overlap on one chip are counted once;
* ``kernel_seconds``: summed device time of the events whose name
  contains a kernel's name;
* ``idle_gaps``: the gaps between busy intervals inside the window,
  each attributed to the host span it falls in (the one it overlaps
  most), or to ``between`` where no span covers it.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]                    # (start_s, end_s)

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
# at 819 GB/s per chip. Keyed by jax's ``device_kind``.
_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}")
    return PEAKS[device_kind]


def busy_union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(busy_union(intervals), lo, hi))


def kernel_seconds(events: Sequence[Tuple[str, float, float]],
                   kernel: str) -> float:
    """Summed duration of the device events whose name holds ``kernel``."""
    return sum(e - s for name, s, e in events if kernel in name)


def op_name(event_name: str) -> str:
    """An op's short name: the HLO instruction's name, without its text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """(name, duration less the events nested in it) for events of one
    line: a while loop's event encloses its body's ops."""
    out, stack = [], []          # [name, end, time of children, duration]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            n, end, child, dur = stack.pop()
            out.append((n, dur - child))
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
    out += [(n, dur - child) for n, _, child, dur in stack]
    return out


def op_totals(events: Sequence[Tuple[str, float, float]], top: int = 10
              ) -> List[Tuple[str, float]]:
    """The ``top`` ops by self time, by short name."""
    tot: Dict[str, float] = defaultdict(float)
    for name, t in self_times(events):
        tot[op_name(name)] += t
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]


def idle_gaps(busy: Sequence[Interval], spans: Sequence[Tuple[str, float, float]],
              lo: float, hi: float) -> List[Tuple[str, float, float]]:
    """[(span name or 'between', start, end)] for every gap in ``busy``
    (a union) within [lo, hi]."""
    gaps, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in gaps:
        best, cover = "between", 0.0
        for name, ss, se in spans:
            ov = min(e, se) - max(s, ss)
            if ov > cover:
                best, cover = name, ov
        out.append((best, s, e))
    return out


def gap_totals(gaps: Sequence[Tuple[str, float, float]], top: int = 10
               ) -> List[Tuple[str, float]]:
    """The longest gaps, named by the host span they fall in."""
    return [(name, e - s) for name, s, e in
            sorted(gaps, key=lambda g: g[1] - g[2])[:top]]


class Trace:
    """Device events and host spans of one traced window, in seconds on
    the profiler's clock."""

    def __init__(self, device: Dict[str, List[Tuple[str, float, float]]],
                 spans: List[Tuple[str, float, float]]):
        self.device = device              # plane -> [(op, start, end)]
        self.spans = spans                # [(name, start, end)] host

    def window(self, name: str) -> Optional[Interval]:
        hits = [(s, e) for n, s, e in self.spans if n == name]
        return (min(s for s, _ in hits), max(e for _, e in hits)) if hits else None

    def events(self) -> List[Tuple[str, float, float]]:
        return [ev for evs in self.device.values() for ev in evs]


def load(logdir: str, span_prefix: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``logdir``: op events from
    each TPU plane's ``XLA Ops`` line, and every host event whose name
    starts with ``span_prefix``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    device, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            device[plane.name] = [
                (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events]
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return Trace(device, spans)
