"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics and
the breakdown read: device busy time, per-module device time, the device
operations that took most time, and the idle gaps labelled by the host span
that was open during each.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Modules`` line has
one event per program execution and ``XLA Ops`` one per operation. Host
spans are the benchmark's own ``TraceAnnotation`` events whose names start
with ``bench.``; the traced window runs from the first such span's start to
the last one's end.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]          # (start, end) in seconds


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps_between(busy: List[Interval], lo: float, hi: float
                 ) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` around the (sorted, disjoint)
    busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def module_name(event_name: str) -> str:
    """``jit_local_decode(123)`` -> ``jit_local_decode``."""
    return event_name.split("(")[0]


def op_name(event_name: str) -> str:
    """An op event is named by its HLO text, ``%fusion.4 = f32[...]
    fusion(...)``: keep the instruction's name, ``%fusion.4``."""
    return event_name.split(" = ")[0]


def read_events(path: str):
    """(device planes, host spans) from one trace file: each device plane
    as ``{"modules": [(name, s, e)], "ops": [(name, s, e)]}``, each span as
    ``(name, s, e)``; times in seconds on the profiler's common clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dev[key].append((ev.name, s, s + ev.duration_ns * 1e-9))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return devices, spans


def reduce(devices, spans, top: int = 10) -> Dict:
    """Busy and window seconds (busy averaged over the device planes), the
    device seconds of each module, the ``top`` operations by device time
    (``module/op``), and the ``top`` longest idle gaps named by the host
    span open at their midpoint (``idle`` where none was)."""
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    if not spans:
        raise ValueError("the trace holds no bench.* host span")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    modules: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    gaps: List[Tuple[float, float]] = []
    for dev in devices:
        mods = sorted(dev["modules"], key=lambda x: x[1])
        for name, s, e in clip_named(mods, lo, hi):
            modules[module_name(name)] = modules.get(
                module_name(name), 0.0) + (e - s)
        evs = dev["ops"] or dev["modules"]
        busy = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        gaps += gaps_between(busy, lo, hi)
        # attribute each op to the module execution that contains it
        j = 0
        for name, s, e in sorted(clip_named(dev["ops"], lo, hi),
                                 key=lambda x: x[1]):
            while j < len(mods) and mods[j][2] < s:
                j += 1
            owner = (module_name(mods[j][0])
                     if j < len(mods) and mods[j][1] <= s else "?")
            key = f"{owner}/{op_name(name)}"
            ops[key] = ops.get(key, 0.0) + (e - s)
    spans_sorted = sorted(spans, key=lambda x: x[1])

    def label(mid: float) -> str:
        best = None
        for name, s, e in spans_sorted:
            if s <= mid <= e and (best is None or s >= best[1]):
                best = (name, s)
        return best[0] if best else "idle"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": hi - lo,
        "busy_s": busy_total / len(devices),
        "modules": modules,
        "top_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label((s + e) / 2), e - s] for s, e in gaps[:top]],
    }


def clip_named(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]


def reduce_file(path: str, top: int = 10) -> Dict:
    return reduce(*read_events(path), top=top)


def module_seconds(red: Dict, patterns) -> float | None:
    """Device seconds of the modules whose names match any regex of
    ``patterns``; None when none ran in the traced window."""
    rx = [re.compile(p) for p in patterns]
    hits = [t for name, t in red["modules"].items()
            if any(r.search(name) for r in rx)]
    return sum(hits) if hits else None
