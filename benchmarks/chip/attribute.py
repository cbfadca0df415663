#!/usr/bin/env python3
"""Run one benchmark cell traced, as ``run.py --trace 1`` does, and add what
the program's own spans say about the device's idle time.

    python3 benchmarks/chip/attribute.py --workload <cell> --seed <n> \\
        --seconds <s>

The run is ``run.py``'s traced run, phases and checks included; the one
difference is that the trace file is read once by ``benchchip/spans.py``,
for both reductions, before it is removed. The last line of standard output is ``run.py``'s result
object with, in addition:

* under ``metrics``: the end-to-end metrics of the traced run (the cost of
  tracing shows there) and the span metrics ``idle_serve_loop``,
  ``idle_runtime``, ``idle_stage`` (percent of the traced window) and
  ``actor_hop_ms``, suffixed like the cell's ``device_idle_share``;
* under ``device``: ``idle_outside_program_s``, the idle seconds no program
  span covered, so that the three idle shares plus this remainder can be
  checked against ``device_idle_share``;
* under ``breakdown``: ``idle_gaps`` named by the innermost span, program
  or ``bench.*``, open at each gap's midpoint;
* ``clock``: host end of each ``stage.sync`` minus the device end of the
  program it waited for (``sync_lag_ms``, in milliseconds), and the idle
  seconds per layer as they would read with the device's intervals moved
  later by the median of that lag: how far the shares hang on the clocks.

Where the program opens no span (a tree without them), the span metrics,
the remainder and ``clock`` are left out and the gaps keep ``run.py``'s
labels.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

import run  # puts src/ and the harness on sys.path, starts the clock
from benchchip import registry, spans, xplane

SPAN_METRICS = (("idle_serve_loop", "%"), ("idle_runtime", "%"),
                ("idle_stage", "%"), ("actor_hop_ms", "ms"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    files = run.load_cell(args.workload)
    devices = run.tpu_devices(files.workload["chips"])
    from benchchip.peaks import peaks_of

    peaks = peaks_of(devices[0].device_kind)
    run.enable_cache()
    bench = registry.benchmark()
    specs = (registry.metrics_for(bench, args.workload, True)
             + registry.metrics_for(bench, args.workload, False))

    # read the trace file run.py reduces once, for run.py's reduction and
    # the program spans', before run.py removes it
    found = {}

    def reduce_both(path, top=10):
        t0 = time.perf_counter()
        devices, bench_spans, program = spans.read_events(path)
        red = xplane.reduce(devices, bench_spans, top=top)
        found["spans"] = spans.reduce(devices, bench_spans, program, top=top)
        run.note(f"trace reduced with the program spans in "
                 f"{time.perf_counter() - t0!r} s")
        return red

    xplane.reduce_file = reduce_both
    result, checks = run.measure(files, specs, seed=args.seed,
                                 seconds=args.seconds, trace=True,
                                 devices=devices, peaks=peaks,
                                 t_start=run.T_START)
    red = found.get("spans")
    if red is not None:
        suffix = next((m["name"][len("device_idle_share"):] for m in specs
                       if m["name"].startswith("device_idle_share")), "")
        rec = types.SimpleNamespace(spans=red)
        for base, unit in SPAN_METRICS:
            value = registry.metric_reader(base + suffix).read(rec)
            if value is not None:
                result["metrics"][base + suffix] = {"value": value,
                                                    "unit": unit}
        result["device"]["idle_outside_program_s"] = red[
            "idle_outside_program_s"]
        result["breakdown"]["idle_gaps"] = red["idle_gaps"]
        result["clock"] = red["clock"]
    result["checks"] = result.pop("checks")
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
