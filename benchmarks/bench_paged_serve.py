"""Paged KV/state cache vs the dense per-slot reservation, serving many
short requests.

The dense serve path (PR 5) reserves ``cache_len`` positions for every
decode slot, sized for the worst-case request — short requests strand most
of it. The paged path backs the same stage programs with a shared page
slab: each request maps only the pages its actual length needs, so the
pool can be sized for the *observed* in-flight load instead of the
worst case.

Both paths serve the identical request mix (10x the slot count, lengths
well under the worst case) through the same 2-stage actor pipeline with an
emulated per-stage device latency, and the paged token streams are gated
bitwise against dense. Gates: the dense cache reservation must be >= 2x
the paged pool bytes, and paged tok/s must stay within 1.15x of dense.

Writes ``BENCH_paged_serve.json``.
"""
import dataclasses
import json
import os
import pathlib
import sys
import time

STAGES = 2
DEVICE_LATENCY = 0.010      # emulated per-stage device time (seconds)
NUM_GROUPS = 2
GROUP_SIZE = 2              # 4 decode slots
MAX_PROMPT_LEN = 16
MAX_NEW_TOKENS = 16
CACHE_LEN = 36              # worst case 16 + 16 < 36, parking slot at 35
PAGE_LEN = 4
NUM_PAGES = 16              # 64 positions vs the dense 4 * 36 = 144


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

    smoke = bool(os.environ.get("BENCH_SMOKE"))
    n_requests = 12 if smoke else 10 * NUM_GROUPS * GROUP_SIZE

    cfg = get_config("qwen2.5-3b").reduced()
    cfg = dataclasses.replace(cfg, vocab_size=1000)   # padded-vocab head
    mesh = make_mesh((1, 1), ("data", "model"))
    params = build_model(cfg, plan_from_mesh(mesh)).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # short requests: prompt + generation - 1 <= 16 positions (4 pages), so
    # four concurrent requests always fit the 16-page pool while the dense
    # path still reserves all 36 positions per slot
    requests = []
    for _ in range(n_requests):
        plen = int(rng.integers(4, 13))
        gen = int(rng.integers(2, 6))
        requests.append(
            (rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32), gen))
    total = sum(g for _, g in requests)

    def with_latency(stage_index, fn):
        def body(payload):
            out = fn(payload)
            time.sleep(DEVICE_LATENCY)
            return out
        return body

    common = dict(mode="serve", params=params, mesh=mesh,
                  num_groups=NUM_GROUPS, group_size=GROUP_SIZE,
                  max_prompt_len=MAX_PROMPT_LEN,
                  max_new_tokens=MAX_NEW_TOKENS, cache_len=CACHE_LEN)
    paged_kw = dict(cache="paged", page_len=PAGE_LEN, num_pages=NUM_PAGES)

    # token-identity reference: dense monolithic greedy
    ref = api.compile(cfg, backend="monolithic", **common).generate(requests)

    def measure(label, **kw):
        sess = api.compile(cfg, backend="actors", stages=STAGES,
                           fn_wrap=with_latency, **common, **kw)
        best, stats = None, None
        reps = 1 if smoke else 2
        for _ in range(reps + 1):     # first rep is the jit warmup
            outs = sess.generate(requests)
            assert all(np.array_equal(a, b) for a, b in zip(outs, ref)), label
            span = sess.last_stats["wall_s"]
            best = span if best is None else min(best, span)
            stats = sess.last_stats
        bytes_ = sess.cache_bytes()
        sess.close()
        return total / best, bytes_, stats

    dense_tok_s, dense_bytes, _ = measure("dense")
    paged_tok_s, paged_bytes, stats = measure("paged", **paged_kw)
    bytes_ratio = dense_bytes / paged_bytes
    slowdown = dense_tok_s / paged_tok_s

    emit("paged_serve/dense", 1e6 * total / dense_tok_s,
         f"tok_s={dense_tok_s:.1f};cache_bytes={dense_bytes}")
    emit("paged_serve/paged", 1e6 * total / paged_tok_s,
         f"tok_s={paged_tok_s:.1f};cache_bytes={paged_bytes};"
         f"bytes_ratio={bytes_ratio:.2f};peak_pages={stats['peak_pages']}")

    out = {
        "stages": STAGES, "requests": n_requests, "total_tokens": total,
        "device_latency_s": DEVICE_LATENCY, "cache_len": CACHE_LEN,
        "page_len": PAGE_LEN, "num_pages": NUM_PAGES,
        "dense_tok_s": dense_tok_s, "paged_tok_s": paged_tok_s,
        "dense_cache_bytes": dense_bytes, "paged_cache_bytes": paged_bytes,
        "cache_bytes_ratio": bytes_ratio,
        "peak_pages": stats["peak_pages"],
        "admitted_mid_flight": stats["admitted_mid_flight"],
    }
    path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_paged_serve.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    if bytes_ratio < 2.0:
        raise RuntimeError(
            f"paged pool saves only {bytes_ratio:.2f}x cache bytes "
            f"({dense_bytes} dense vs {paged_bytes} paged); gate is 2x")
    if slowdown > 1.15:
        raise RuntimeError(
            f"paged decode {paged_tok_s:.1f} tok/s is {slowdown:.2f}x "
            f"slower than dense {dense_tok_s:.1f} tok/s; gate is 1.15x")
    if stats["admitted_mid_flight"] < 1:
        raise RuntimeError("no request was admitted mid-flight")


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        os.environ["BENCH_SMOKE"] = "1"
    main()
