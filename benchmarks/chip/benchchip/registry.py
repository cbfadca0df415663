"""Find every per-configuration, per-mix, per-cell and per-metric file by
the names ``BENCHMARK.json`` gives, so that a later cell or metric is added
by adding files, with no edit to one that exists:

* ``configs/<config>.json``  the configuration as run, and
  ``configs/<config>.py``    its weights, plain reference and work counts;
* ``traffic/<traffic>.json`` the mix's parameters (:mod:`benchchip.traffic`);
* ``cells/<workload>.json``  the cell's ``serve`` object, passed whole to
  ``api.compile`` (:func:`benchchip.serve.serve_options`: cache, slots,
  chunking, mesh, stages), its ``load`` (the mix's rate) and its ``check``
  (sample size, reference block, limits);
* ``metrics/<metric>.py``    a reader ``read(run) -> float | None``; a
  metric ``base.suffix`` without a file of its own uses ``base.py``.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]      # the benchmark's root
REPO = HERE.parents[1]                                   # the checkout


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    """Import the file ``path`` as module ``name`` (once per process)."""
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def benchmark() -> dict:
    return load_json(REPO / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_spec(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def config_module(name: str):
    return load_module(HERE / "configs" / f"{name}.py",
                       "benchchip_config_" + name.replace("-", "_")
                       .replace(".", "_"))


def traffic_mix(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def cell(name: str) -> dict:
    return load_json(HERE / "cells" / f"{name}.json")


def metric_reader(name: str):
    own = HERE / "metrics" / f"{name}.py"
    path = own if own.is_file() else HERE / "metrics" / (
        name.split(".")[0] + ".py")
    return load_module(path, "benchchip_metric_" + path.stem.replace(
        ".", "_").replace("-", "_"))


def metrics_for(bench: dict, workload_name: str, trace: bool):
    """The cell's metrics: its end-to-end ones without tracing, its
    per-layer ones with it (a metric with a ``workloads`` list applies only
    to those cells)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if workload_name in m.get("workloads", [workload_name])]
