"""``run.py``'s phases at tiny widths on the CPU: set-up, window, metrics
and the correctness check, as on the chip; and the command itself, which
refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import benchchip_tiny as tiny
from benchchip import registry, serve
from benchchip.peaks import PEAKS

SEED = 2**33 + 5


@pytest.mark.parametrize("kind,metrics", [
    ("qwen", ("setup_s", "serve_tok_s", "tokens_per_round", "round_ms",
              "step_mfu")),
    ("mamba", ("setup_s", "req_latency_p95_s", "queue_wait_p50_s",
               "tokens_per_round", "round_ms", "step_mfu")),
])
def test_tiny_cell_runs_and_is_correct(kind, metrics):
    result, checks = tiny.measure(kind, SEED, metrics=metrics)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == set(metrics)
    for m in result["metrics"].values():
        assert np.isfinite(m["value"]) and m["value"] > 0
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"failed_requests", "mean_logit_gap"}
    assert result["device"]["platform"] == "cpu"
    assert checks["mean_logit_gap"][0] <= tiny.TINY_LIMIT


@pytest.mark.parametrize("serve", [
    {"cache": "dense"},
    {"prefill_chunk": 16},
    {"mesh": [1, 1], "stages": 1},
])
def test_cell_serve_options_reach_the_session(serve, monkeypatch):
    """A cell's ``serve`` object passes to ``api.compile`` whole, so a later
    cell chooses its cache, chunking, mesh or stages in its own file."""
    from repro import api

    seen = {}
    orig = api.compile

    def spy(*a, **k):
        seen.update(k)
        return orig(*a, **k)
    monkeypatch.setattr(api, "compile", spy)
    result, _ = tiny.measure("qwen", SEED, metrics=("serve_tok_s",), **serve)
    assert result["correct"] is True and result["failed"] == 0
    for k, v in serve.items():
        if k == "mesh":
            assert dict(seen["mesh"].shape) == {"data": 1, "model": 1}
        else:
            assert seen[k] == v
    assert seen["backend"] == "actors"
    assert seen["cache"] == serve.get("cache", "paged")


def test_device_metrics_need_a_trace():
    """Without a trace the device readers find nothing to read and the
    harness leaves them out rather than report 0."""
    result, _ = tiny.measure("qwen", SEED, metrics=(
        "decode_roofline.batch", "device_idle_share.batch", "round_ms"))
    assert set(result["metrics"]) == {"round_ms"}


def test_command_refuses_a_cpu_backend():
    """No TPU: exit non-zero and print no result line."""
    proc = subprocess.run(
        [sys.executable, str(registry.HERE / "run.py"), "--workload",
         "qwen3-1.7b.serve.longdoc-batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_benchmark_json_names_files_that_exist():
    """Every configuration, mix, cell and metric named in BENCHMARK.json
    has its files, so the harness finds each by name."""
    bench = registry.benchmark()
    for c in bench["configs"]:
        spec = registry.config_spec(c["name"])
        assert spec["name"] == c["name"]
        assert (registry.REPO / c["file"]).is_file()
        mod = registry.config_module(c["name"])
        for fn in ("model_kwargs", "init_weights", "reference_logits",
                   "token_flops", "prefill_flops", "decode_work"):
            assert callable(getattr(mod, fn))
    for w in bench["workloads"]:
        registry.traffic_mix(w["traffic"])
        limits = registry.cell(w["name"])["check"]["limits"]
        assert limits and all(v > 0 for v in limits.values())
        assert set(limits) <= set(serve.gap_stats(np.zeros(1)))
        for trace in (False, True):
            for m in registry.metrics_for(bench, w["name"], trace):
                assert callable(registry.metric_reader(m["name"]).read)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))


def test_result_line_is_json_last(capsys, monkeypatch):
    """main() prints the checks on standard error and the result object as
    the last line of standard output."""
    R = tiny.run_module()
    files = tiny.tiny_files("qwen")
    monkeypatch.setattr(R, "load_cell", lambda name: files)
    monkeypatch.setattr(R, "tpu_devices", lambda chips: jax.devices())
    monkeypatch.setattr(R, "enable_cache", lambda: None)
    monkeypatch.setattr(R.registry, "metrics_for",
                        lambda bench, name, trace: [
                            {"name": "serve_tok_s", "unit": "tokens/s"}])
    monkeypatch.setattr("benchchip.peaks.peaks_of",
                        lambda kind: PEAKS["TPU v5 lite"])
    assert R.main(["--workload", "tiny-qwen", "--seed", str(SEED),
                   "--seconds", "0.5", "--trace", "0"]) == 0
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert line["metrics"]["serve_tok_s"]["unit"] == "tokens/s"
    err = cap.err.strip().splitlines()
    assert err[-2].startswith("check failed_requests 0 limit 0")
    assert err[-1].startswith("check mean_logit_gap")
