#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, serving geometry and metric readers are files
under this directory found by name (``benchchip/registry.py``). A run:

1. refuses to start unless JAX finds a TPU with the chips the cell needs,
   and names the device;
2. builds the weights from the seed, compiles the serving session (JAX's
   persistent compilation cache at ``<checkout>/.jax_cache`` unless
   ``JAX_COMPILATION_CACHE_DIR`` is set), warms up every prompt length the
   mix can draw: that is ``setup_s``;
3. measures a window of ``--seconds`` (``benchchip/serve.py``); with
   ``--trace 1`` the profiler records it and ``benchchip/xplane.py`` reduces
   the trace;
4. reads the peak device memory, frees the session, and compares a sample
   of the served tokens, drawn from the seed, with the configuration's plain
   float32 reference: ``correct``;
5. prints the compared numbers beside their limits as the last lines of
   standard error, and one JSON object as the last line of standard output:
   the end-to-end metrics without tracing, the per-layer ones with it.

Lines tagged ``[setup]`` on standard error describe the run; they are not
measurements of the system's speed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

# libtpu writes its logs to a fixed /tmp/tpu_logs unless told otherwise; a
# run writes only inside its checkout and its own temporary directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from benchchip import registry  # noqa: E402


def note(msg: str) -> None:
    print(f"[setup] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class CellFiles:
    """Everything one cell is made of, loaded from its files."""

    workload: dict
    spec: dict
    module: Any
    mix: dict
    cell: dict


def load_cell(name: str) -> CellFiles:
    bench = registry.benchmark()
    w = registry.workload(bench, name)
    return CellFiles(w, registry.config_spec(w["config"]),
                     registry.config_module(w["config"]),
                     registry.traffic_mix(w["traffic"]),
                     registry.cell(name))


@dataclasses.dataclass
class RunRecord:
    """What a metric reader reads (``metrics/<name>.py``)."""

    workload: str
    spec: dict
    module: Any
    peaks: dict
    chips: int
    setup_s: float
    window: Any
    trace: Optional[Dict] = None
    decode_ctxs: Optional[List[List[int]]] = None


def tpu_devices(chips: int):
    """The TPU devices, or a reason there are not enough."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chips, JAX found "
                         f"{len(devs)}")
    return devs


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or ``JAX_COMPILATION_CACHE_DIR``), every program cached."""
    import jax

    from repro.launch.xla_env import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def measure(files: CellFiles, metric_specs: List[dict], *, seed: int,
            seconds: float, trace: bool, devices, peaks: dict,
            t_start: float):
    """Set up, run the window, read ``metric_specs`` (entries of
    ``BENCHMARK.json``), check the served tokens. Returns the result object
    and the compared numbers ``{name: (value, limit)}``."""
    import jax

    from benchchip import serve, xplane

    name = files.workload["name"]
    counter = serve.CompileCounter()
    cell = serve.ServeCell(files.cell, files.spec, files.module, files.mix,
                           seed, record=trace)
    cell.warm_up()
    setup_s = time.perf_counter() - t_start
    note(f"set-up {setup_s!r} s (weights, api.compile, warm-up of "
         f"{len(cell.traffic.prompt_buckets())} prompt lengths)")

    trace_dir = tempfile.mkdtemp(prefix="benchchip-trace-") if trace else None
    if trace:
        # device ops and the harness's TraceAnnotation spans are all the
        # reduction reads; a Python call trace and HLO protos would multiply
        # the trace's size and the time to write and read it
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        win = cell.run_window(seconds, counter, jax.profiler.TraceAnnotation)
    finally:
        if trace:
            jax.profiler.stop_trace()
    note(f"window: {len(win.calls)} calls, {win.attempted} requests, "
         f"span {win.span!r} s, programs lowered inside it: {win.lowerings}")
    red = ctxs = None
    if trace:
        red = xplane.reduce_file(xplane.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctxs = cell.recorder.decode_ctxs(calls=range(len(win.calls)))

    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:files.workload["chips"]])
    cell.close()
    del cell
    gc.collect()

    run = RunRecord(name, files.spec, files.module, peaks,
                    files.workload["chips"], setup_s, win, red, ctxs)
    metrics = {}
    for m in metric_specs:
        value = registry.metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # correctness, once the program's state is freed
    t0 = time.perf_counter()
    chk = files.cell["check"]
    failed = serve.failed_requests(win, files.module.model_kwargs(
        files.spec)["vocab_size"])
    picked = serve.sample(win, seed, chk["tokens"], chk["max_requests"])
    weights = serve.make_weights(files.module, files.spec, seed)
    gaps = serve.reference_gaps(files.module, files.spec, weights, picked,
                                block=chk.get("block"))
    del weights
    stats = serve.gap_stats(gaps)
    checks = {"failed_requests": (failed, 0)}
    checks.update({k: (stats[k], lim) for k, lim in chk["limits"].items()})
    correct = all(v <= lim for v, lim in checks.values())
    note(f"reference over {len(picked)} requests, {gaps.size} served "
         f"tokens in {time.perf_counter() - t0!r} s: {json.dumps(stats)}")

    d0 = devices[0]
    result = {"correct": bool(correct), "attempted": win.attempted,
              "failed": int(failed), "metrics": metrics,
              "device": {"platform": d0.platform, "kind": d0.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": mem_peak}}
    if trace:
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": [list(x) for x in
                                              red["top_ops"]],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    files = load_cell(args.workload)
    devices = tpu_devices(files.workload["chips"])
    from benchchip.peaks import peaks_of

    peaks = peaks_of(devices[0].device_kind)
    enable_cache()
    import jax

    note(f"device {devices[0].device_kind!r} x{len(devices)}, jax "
         f"{jax.__version__}, compile cache "
         f"{jax.config.jax_compilation_cache_dir!r}")
    specs = registry.metrics_for(registry.benchmark(), args.workload,
                                 bool(args.trace))
    result, checks = measure(files, specs, seed=args.seed,
                             seconds=args.seconds,
                             trace=bool(args.trace), devices=devices,
                             peaks=peaks, t_start=T_START)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
