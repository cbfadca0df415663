"""Continuous-batching serve pipeline vs serialized single-request decode.

Eight requests with unequal generation lengths are served two ways through
the SAME 2-stage actor pipeline (repro.api mode="serve"):

* serialized: 1 group x 1 slot — one request decodes at a time, one token
  per round, no admission overlap (the classic request-at-a-time server);
* continuous batching: 2 groups x 2 slots — every round advances 4 requests
  by a token, groups overlap across the stage actors under the forward
  register quotas, and retired slots are refilled from the queue mid-flight.

Host CPU cores cannot stand in for busy accelerators, so each stage body
adds a fixed DEVICE_LATENCY sleep emulating the device-side decode step the
host thread would block on — the jitted stage computation itself is real,
and the continuous-batching token streams are gated against the monolithic
whole-stack engine, token for token.

Writes ``BENCH_serve_pipeline.json`` (tok/s both ways + speedup) so the
serving-throughput trajectory is recorded across PRs.
"""
import dataclasses
import json
import os
import pathlib
import sys
import time

STAGES = 2
PROMPT_LEN = 8
GENS = [6, 3, 5, 4, 6, 2, 4, 6]     # 8 requests, 36 tokens, unequal lengths
DEVICE_LATENCY = 0.010              # emulated per-stage device time (seconds)


def main():
    sys.path.insert(0, "src")
    import numpy as np

    from benchmarks._util import emit
    from repro import api
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.model_zoo import build_model
    from repro.train.steps import plan_from_mesh

    import jax

    cfg = get_config("qwen2.5-3b").reduced()
    cfg = dataclasses.replace(cfg, vocab_size=1000)   # padded-vocab head
    mesh = make_mesh((1, 1), ("data", "model"))
    params = build_model(cfg, plan_from_mesh(mesh)).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    requests = [
        (rng.integers(0, cfg.vocab_size, (PROMPT_LEN,)).astype(np.int32), g)
        for g in GENS]
    total = sum(GENS)

    def with_latency(stage_index, fn):
        def body(payload):
            out = fn(payload)
            time.sleep(DEVICE_LATENCY)
            return out
        return body

    common = dict(mode="serve", params=params, mesh=mesh,
                  max_prompt_len=PROMPT_LEN, max_new_tokens=max(GENS))

    # token-identity reference: the monolithic whole-stack engine
    ref = api.compile(cfg, backend="monolithic", num_groups=2, group_size=2,
                      **common).generate(requests)

    def measure(label, **kw):
        sess = api.compile(cfg, backend="actors", stages=STAGES,
                           fn_wrap=with_latency, **common, **kw)
        best = None
        reps = 1 if os.environ.get("BENCH_SMOKE") else 2
        for _ in range(reps + 1):     # first rep is the jit warmup
            outs = sess.generate(requests)
            assert all(np.array_equal(a, b) for a, b in zip(outs, ref)), label
            span = sess.last_stats["wall_s"]
            best = span if best is None else min(best, span)
        return total / best, sess.last_stats

    serialized_tok_s, _ = measure("serialized", num_groups=1, group_size=1,
                                  regs=[1] * STAGES)
    pipelined_tok_s, stats = measure("continuous", num_groups=2, group_size=2)
    speedup = pipelined_tok_s / serialized_tok_s

    emit("serve_pipeline/serialized_1x1", 1e6 * total / serialized_tok_s,
         f"tok_s={serialized_tok_s:.1f}")
    emit("serve_pipeline/continuous_2x2", 1e6 * total / pipelined_tok_s,
         f"tok_s={pipelined_tok_s:.1f};speedup={speedup:.2f};"
         f"admitted_mid_flight={stats['admitted_mid_flight']}")

    out = {
        "stages": STAGES, "requests": len(GENS), "prompt_len": PROMPT_LEN,
        "total_tokens": total, "device_latency_s": DEVICE_LATENCY,
        "serialized_tok_s": serialized_tok_s,
        "pipelined_tok_s": pipelined_tok_s,
        "speedup": speedup,
        "admitted_mid_flight": stats["admitted_mid_flight"],
    }
    path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_serve_pipeline.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    if stats["admitted_mid_flight"] < 1:
        raise RuntimeError("no request was admitted mid-flight")
    if speedup < 1.5:
        raise RuntimeError(
            f"continuous batching {pipelined_tok_s:.1f} tok/s is under "
            f"1.5x the serialized {serialized_tok_s:.1f} tok/s")


if __name__ == "__main__":
    main()
