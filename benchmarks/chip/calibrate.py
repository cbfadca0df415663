#!/usr/bin/env python3
"""Readings the benchmark's limits and rates were set from (not part of a
benchmark run; run on the chip, one process).

    python3 benchmarks/chip/calibrate.py readings --workload <cell> \\
        --seeds 1,2,3 --seconds 10 [--control float8_e4m3fn]
    python3 benchmarks/chip/calibrate.py sweep --workload <cell> \\
        --rates 4,8,16 --seconds 30 --seed 1

``readings``: for each seed, the cell as a run builds it (weights from the
seed), a window of ``--seconds`` at the cell's own load, then the numbers a
run compares (:func:`benchchip.serve.gap_stats`) for the served tokens
against the float32 reference and, with ``--control``, for the tokens a
float8 run of the reference puts first at the same positions. ``sweep``:
one session, a window at each open-loop rate, and what shows a growing
backlog (queue waits late in the window against early, and how long the
last requests took to drain).

Each reading is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def _setup(workload: str):
    from benchchip import registry

    run = registry.load_module(HERE / "run.py", "benchchip_run")
    files = run.load_cell(workload)
    run.tpu_devices(files.workload["chips"])
    run.enable_cache()
    return files


def readings(args) -> None:
    import jax

    from benchchip import serve

    files = _setup(args.workload)
    chk = files.cell["check"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        cell = serve.ServeCell(files.cell, files.spec, files.module,
                               files.mix, seed)
        cell.warm_up()
        setup = time.perf_counter() - t0
        win = cell.run_window(args.seconds, serve.CompileCounter(),
                              jax.profiler.TraceAnnotation)
        cell.close()
        del cell
        picked = serve.sample(win, seed, chk["tokens"], chk["max_requests"])
        weights = serve.make_weights(files.module, files.spec, seed)
        t1 = time.perf_counter()
        prog = serve.reference_gaps(files.module, files.spec, weights,
                                    picked, block=chk.get("block"))
        ref_s = time.perf_counter() - t1
        out = {"seed": seed, "setup_s": setup, "requests": win.attempted,
               "checked_tokens": int(prog.size), "reference_s": ref_s,
               **serve.gap_stats(prog)}
        if args.control:
            ctrl = serve.reference_gaps(files.module, files.spec, weights,
                                        picked, lowp=args.control,
                                        block=chk.get("block"))
            out.update({"control_" + k: v
                        for k, v in serve.gap_stats(ctrl).items()})
        del weights
        print(json.dumps(out), flush=True)


def sweep(args) -> None:
    import jax
    import numpy as np

    from benchchip import serve
    from benchchip.traffic import Traffic

    files = _setup(args.workload)
    cell = serve.ServeCell(files.cell, files.spec, files.module, files.mix,
                           args.seed)
    cell.warm_up()
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = Traffic(dict(files.mix, rate_per_s=rate),
                               vocab=cell.cfg.vocab_size, seed=args.seed,
                               slots=cell.slots)
        win = cell.run_window(args.seconds, serve.CompileCounter(),
                              jax.profiler.TraceAnnotation)
        served = sorted(win.served, key=lambda s: s.req.due)
        lat = np.array([s.end - s.req.due for s in served])
        wait = np.array([s.start - s.req.due for s in served])
        q = max(1, len(served) // 4)
        print(json.dumps({
            "rate_per_s": rate, "requests": win.attempted,
            "calls": len(win.calls),
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p95_s": float(np.percentile(lat, 95)),
            "queue_wait_first_quarter_s": float(wait[:q].mean()),
            "queue_wait_last_quarter_s": float(wait[-q:].mean()),
            "drain_s": win.calls[-1]["end"] - args.seconds,
            "tokens_per_s": sum(len(s.tokens) for s in served) / win.span,
        }), flush=True)
    cell.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    r = sub.add_parser("readings")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--control", default=None)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, required=True)
    s.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    (readings if args.what == "readings" else sweep)(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
