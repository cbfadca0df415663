"""Read the program's own spans from a profiler trace and charge every idle
interval of the device to the layer whose span was open.

The serve path and the actor runtime open ``jax.profiler.TraceAnnotation``
spans at their layer boundaries; they land on the host planes of the same
``.xplane.pb`` as the device planes, on the profiler's common clock:

* ``serve.*`` (serve loop): ``serve.generate``, ``serve.plan``,
  ``serve.absorb``;
* ``runtime.*`` (runtime): ``runtime.round``, ``runtime.epoch_start``,
  ``runtime.epoch_end``;
* ``actor.fire`` (metadata ``actor``, ``epoch``, ``fire``: the actor hop
  and the clock check) and ``stage.gather``, ``stage.program``,
  ``stage.sync``, ``stage.scatter`` (``kind``: the clock check): the stage
  fires.

Attribution: each idle interval of a device (as :mod:`benchchip.xplane`
finds them, inside the window the ``bench.*`` spans bound) is split at span
boundaries, and each piece is charged to the innermost open program span,
the latest-started one still open on any thread; a piece with none open is
outside the program. The layers' seconds plus the outside remainder sum to
the window's idle seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import re
import statistics
from typing import Dict, List, Optional

from benchchip import xplane

LAYERS = (("serve.", "serve_loop"), ("runtime.", "runtime"),
          ("actor.", "stage"), ("stage.", "stage"))
PROGRAM_PREFIXES = tuple(p for p, _ in LAYERS)
#: the stage program a ``stage.sync`` of each ``kind`` waits for
SYNCED_PROGRAM = {"decode": "local_decode", "prefill": "local_prefill",
                  "chunk": "local_chunk"}


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float                  # seconds on the profiler's clock
    end: float
    thread: tuple                 # (host plane, line index): one thread
    stats: Dict


def layer_of(name: str) -> str:
    for prefix, layer in LAYERS:
        if name.startswith(prefix):
            return layer
    raise ValueError(f"{name!r} is not a program span")


def read_events(path: str):
    """One walk of the trace file: the device planes and the ``bench.*``
    spans as :func:`xplane.read_events` gives them, and every program span
    on the host planes with its thread line and its metadata."""
    from jax.profiler import ProfileData

    devices, bench, program = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key is not None:
                    dev[key] += [(ev.name, *_seconds(ev))
                                 for ev in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(xplane.SPAN_PREFIX):
                        bench.append((ev.name, *_seconds(ev)))
                    elif ev.name.startswith(PROGRAM_PREFIXES):
                        program.append(Span(ev.name, *_seconds(ev),
                                            (plane.name, i), dict(ev.stats)))
    return devices, bench, program


def _seconds(ev) -> xplane.Interval:
    s = ev.start_ns * 1e-9
    return s, s + ev.duration_ns * 1e-9


def attribute(idle: List[xplane.Interval], spans: List[Span]
              ) -> Dict[Optional[str], float]:
    """Seconds of the (sorted, disjoint) ``idle`` intervals charged to each
    layer, ``None`` for the pieces outside every program span."""
    charged: Dict[Optional[str], float] = {
        layer: 0.0 for _, layer in LAYERS}
    charged[None] = 0.0
    # (time, order, index): at one instant every boundary is applied
    # before the next piece is charged, so their order there is free
    events = [(t, k, i) for i, sp in enumerate(spans)
              for t, k in ((sp.start, 1), (sp.end, 0))]
    events += [(t, k, -1) for s, e in idle for t, k in ((s, 2), (e, 3))]
    events.sort()
    # open spans, innermost first: latest start, then earliest end
    heap: List[tuple] = []
    closed = [False] * len(spans)
    idle_open, t_prev = False, None
    for t, kind, i in events:
        if idle_open and t > t_prev:
            while heap and closed[heap[0][2]]:
                heapq.heappop(heap)
            layer = layer_of(spans[heap[0][2]].name) if heap else None
            charged[layer] += t - t_prev
        t_prev = t
        if kind == 1:
            heapq.heappush(heap, (-spans[i].start, spans[i].end, i))
        elif kind == 0:
            closed[i] = True
        else:
            idle_open = kind == 2
    return charged


def actor_hops(spans: List[Span]) -> List[float]:
    """Per stage pair ``s -> s+1``, epoch ``e`` and fire ``k``: the start of
    ``stage{s+1}``'s fire minus the later of the end of ``stage{s}``'s fire
    ``k`` (its input) and of ``stage{s+1}``'s own fire ``k-1`` (the consumer
    busy): the hand-off latency once both were free, in seconds."""
    fires = {}
    for sp in spans:
        if sp.name == "actor.fire":
            fires[(sp.stats["actor"], sp.stats["epoch"],
                   sp.stats["fire"])] = sp
    hops = []
    for (actor, e, k), sp in fires.items():
        m = re.fullmatch(r"stage(\d+)", str(actor))
        if m is None or m[1] == "0":
            continue
        prod = fires.get((f"stage{int(m[1]) - 1}", e, k))
        if prod is None:
            continue
        prev = fires.get((actor, e, k - 1))
        ready = max(prod.end, prev.end) if prev is not None else prod.end
        hops.append(sp.start - ready)
    return hops


def sync_lags(devices, spans: List[Span]) -> List[float]:
    """Per ``stage.sync`` span: its end (host clock) minus the device end of
    the stage program it waited for, the last execution of that program
    that started before the sync ended. The program is the one of the
    sync's ``kind`` on the stage of the ``actor.fire`` around it on the
    same thread (any stage where none is). With the two clocks agreeing
    this is the time the host took to see the device finish: small and
    positive."""
    runs: Dict[str, List[tuple]] = {}
    for dev in devices:
        for name, s, e in dev["modules"]:
            runs.setdefault(xplane.module_name(name), []).append((s, e))
    for v in runs.values():
        v.sort()
    fires: Dict[tuple, List[Span]] = {}
    for sp in spans:
        if sp.name == "actor.fire":
            fires.setdefault(sp.thread, []).append(sp)
    starts = {}
    for th, v in fires.items():
        v.sort(key=lambda sp: sp.start)
        starts[th] = [sp.start for sp in v]
    lags = []
    for sp in spans:
        prog = (SYNCED_PROGRAM.get(sp.stats.get("kind"))
                if sp.name == "stage.sync" else None)
        if prog is None:
            continue
        own = fires.get(sp.thread, [])
        j = bisect.bisect_right(starts.get(sp.thread, []), sp.start) - 1
        stage = (re.fullmatch(r"stage(\d+)", str(own[j].stats["actor"]))
                 if j >= 0 and own[j].end >= sp.end else None)
        rx = re.compile(rf"^jit_{prog}_s{stage[1] if stage else r'\d+'}$")
        best = None
        for name, rs in runs.items():
            if rx.match(name):
                k = bisect.bisect_left(rs, (sp.end, float("inf"))) - 1
                if k >= 0 and (best is None or rs[k][0] > best[0]):
                    best = rs[k]
        if best is not None:
            lags.append(sp.end - best[1])
    return lags


def label_gaps(gaps: List[xplane.Interval], spans, top: int = 10):
    """The ``top`` longest ``gaps`` (in the order :func:`xplane.reduce`
    gives them), each named by the innermost span, program or ``bench.*``,
    open at its midpoint (``idle`` where none was). ``spans`` are
    ``(name, start, end)``."""
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    out = []
    for s, e in gaps:
        mid, best = (s + e) / 2, None
        for name, a, b in spans:
            if a <= mid <= b and (best is None or (a, -b) > best[1:]):
                best = (name, a, -b)
        out.append([best[0] if best else "idle", e - s])
    return out


def reduce(devices, bench_spans, spans: List[Span], top: int = 10
           ) -> Optional[Dict]:
    """The program spans' reduction, or None when the trace holds none:
    device-idle seconds per layer (``idle_s``) and outside the program
    (``idle_outside_program_s``), averaged over the device planes as
    ``busy_s`` is; the mean actor hop (``actor_hop_ms``); the ``top`` idle
    gaps named by program or ``bench.*`` span; and the clock check
    (``clock``): the median, least, largest and count of
    :func:`sync_lags` in milliseconds, and what the idle seconds would be
    with the device's intervals moved later by the median lag. The shares
    themselves are never moved."""
    if not spans:
        return None
    lo = min(s for _, s, _ in bench_spans)
    hi = max(e for _, _, e in bench_spans)
    inside = [sp for sp in spans if sp.end > lo and sp.start < hi]
    busy, idles = 0.0, []
    for dev in devices:
        evs = dev["ops"] or dev["modules"]
        on = xplane.union(xplane.clip([(s, e) for _, s, e in evs], lo, hi))
        busy += sum(e - s for s, e in on)
        idles.append(xplane.gaps_between(on, lo, hi))
    n = len(devices)

    def charge(shift: float) -> Dict[Optional[str], float]:
        total: Dict[Optional[str], float] = {}
        for idle in idles:
            moved = [(s + shift, e + shift) for s, e in idle]
            for layer, t in attribute(moved, inside).items():
                total[layer] = total.get(layer, 0.0) + t / n
        return total

    charged = charge(0.0)
    hops = actor_hops(inside)
    lags = sync_lags(devices, inside)
    clock = None
    if lags:
        med = statistics.median(lags)
        clock = {"sync_lag_ms": {"median": 1e3 * med, "min": 1e3 * min(lags),
                                 "max": 1e3 * max(lags), "count": len(lags)},
                 "idle_s_moved_by_median_lag": {
                     k or "outside": v for k, v in charge(med).items()}}
    named = list(bench_spans) + [(sp.name, sp.start, sp.end)
                                 for sp in inside]
    return {
        "window_s": hi - lo,
        "busy_s": busy / n,
        "idle_s": {k: v for k, v in charged.items() if k is not None},
        "idle_outside_program_s": charged[None],
        "actor_hop_ms": 1e3 * statistics.fmean(hops) if hops else None,
        "clock": clock,
        "idle_gaps": label_gaps(sum(idles, []), named, top),
    }


def idle_share(run, layer: str) -> Optional[float]:
    """Percent of the traced window in which the device was idle and the
    innermost open program span belonged to ``layer``; None where the run
    was not traced or the program opened no span."""
    red = getattr(run, "spans", None)
    if not red:
        return None
    return 100.0 * red["idle_s"][layer] / red["window_s"]
