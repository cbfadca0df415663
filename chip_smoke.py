#!/usr/bin/env python3
"""Bring-up check on a TPU: the repo's main paths, run once, checked.

    python3 chip_smoke.py              # one chip: kernels, serve, train
    python3 chip_smoke.py --chips 4    # four chips: TP=4 serve and the
                                       # stage-mesh 1F1B graph, each against
                                       # the same work on one chip

Phases (one process; a failed check raises and the script exits non-zero):

* kernels — the four Pallas kernels compiled (``interpret=False``) at real
  widths, each checked against its ``ref.py`` oracle (computed at full fp32
  matmul precision) with the tolerances of ``tests/test_kernels.py``.
* serve — qwen3-1.7b at its published widths (28 layers, random weights from
  ``PRNGKey(0)``) through ``api.compile(mode="serve", backend="actors")``:
  8 requests from a seed, dense cache, then the same requests with
  ``cache="paged"``; the token ids must be identical.
* train — the 1F1B actor pipeline on an 8-layer matmul+relu graph at width
  2048 with a softmax-xent head and AdamW, 3 steps against
  ``backend="monolithic"`` (see :func:`compare_train`).

Lines tagged ``[setup]`` are set-up information (versions, compile and run
wall times, memory), not measurements of the system's speed. The last line
of standard output is a JSON object naming the device. The script exits
non-zero before any phase when JAX finds no TPU; it has no CPU mode. The
phase functions take their sizes as arguments so the tests can run them at
reduced size on the CPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.graph import LogicalGraph  # noqa: E402
from repro.core.lowering import OptimizerSpec  # noqa: E402
from repro.core.placement import Placement  # noqa: E402
from repro.kernels.flash_attention.kernel import flash_attention_pallas  # noqa: E402
from repro.kernels.flash_attention.ref import attention_dense_ref  # noqa: E402
from repro.kernels.flash_decode.kernel import flash_decode_pallas  # noqa: E402
from repro.kernels.flash_decode.ref import decode_attention_ref  # noqa: E402
from repro.kernels.softmax_xent.kernel import xent_local_stats_pallas  # noqa: E402
from repro.kernels.softmax_xent.ref import local_stats_ref  # noqa: E402
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas  # noqa: E402
from repro.kernels.ssd_scan.ref import ssd_sequential_ref  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.xla_env import enable_compile_cache  # noqa: E402

#: bf16 tolerance of tests/test_kernels.py (every kernel input here is bf16)
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)

#: real widths: qwen3-1.7b attention (16 q / 8 kv heads x 128), a quarter
#: of its 151 936 vocab, mamba2-370m's SSD (32 heads x 64, state 128)
KERNEL_WIDTHS = {
    "flash_attention": dict(batch=1, seq=2048, heads=16, kv_heads=8,
                            head_dim=128),
    "flash_decode": dict(batch=4, cache=4096, heads=16, kv_heads=8,
                         head_dim=128),
    "softmax_xent": dict(rows=1024, vocab=37984),
    "ssd_scan": dict(batch=1, seq=2048, heads=32, head_dim=64, state=128,
                     chunk=128),
}

TRAIN_WIDTHS = dict(width=2048, layers=8, batch=1024, stages=4,
                    microbatches=8, steps=3)

SERVE_SHAPE = dict(stages=2, num_groups=2, group_size=4, max_prompt_len=512,
                   max_new_tokens=32, cache_len=1024)


def setup(msg: str) -> None:
    print(f"[setup] {msg}", flush=True)


def peak_bytes() -> str:
    """``peak_bytes_in_use`` of every local device, where reported."""
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            out.append(f"{d.id}:{stats['peak_bytes_in_use']}")
    return " ".join(out) or "not reported by this backend"


def _timed(fn, *args):
    """Compile ``fn`` for ``args``, then run it once: (out, compile_s,
    run_s)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _ref(fn, *args, static_argnums=()):
    """An oracle over the same values upcast to fp32, at full fp32 matmul
    precision (the TPU's default runs fp32 matmuls in bf16 passes)."""
    args = [a.astype(jnp.float32)
            if isinstance(a, jax.Array) and jnp.issubdtype(a.dtype,
                                                            jnp.floating)
            else a for a in args]
    with jax.default_matmul_precision("float32"):
        return jax.jit(fn, static_argnums=static_argnums)(*args)


def _check_close(name: str, got, want, tol) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite kernel output"
    np.testing.assert_allclose(got, want, err_msg=name, **tol)
    setup(f"  {name}: max abs diff vs ref {np.max(np.abs(got - want))!r}")


def phase_kernels(widths=None, *, interpret: bool = False, seed: int = 0):
    """Run each Pallas kernel at ``widths`` and check it against its
    oracle; returns ``{kernel: (compile_s, run_s)}``."""
    widths = KERNEL_WIDTHS if widths is None else widths
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, dtype=bf, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    times = {}
    w = widths["flash_attention"]
    q = normal((w["batch"], w["seq"], w["heads"], w["head_dim"]))
    k = normal((w["batch"], w["seq"], w["kv_heads"], w["head_dim"]))
    v = normal((w["batch"], w["seq"], w["kv_heads"], w["head_dim"]))
    out, *times["flash_attention"] = _timed(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=True, interpret=interpret), q, k, v)
    want = _ref(attention_dense_ref, q, k, v)
    _check_close("flash_attention", out, want, KERNEL_TOL)

    w = widths["flash_decode"]
    B, L = w["batch"], w["cache"]
    q = normal((B, w["heads"], w["head_dim"]))
    k = normal((B, L, w["kv_heads"], w["head_dim"]))
    v = normal((B, L, w["kv_heads"], w["head_dim"]))
    cur = jax.random.randint(next(keys), (B,), L // 4, L, jnp.int32)
    (m, l, acc), *times["flash_decode"] = _timed(
        lambda q, k, v, c: flash_decode_pallas(
            q, k, v, cur_pos=c, interpret=interpret), q, k, v, cur)
    got = acc / jnp.maximum(l, 1e-30)[..., None]
    want = _ref(decode_attention_ref, q, k, v, cur)
    _check_close("flash_decode", got, want, KERNEL_TOL)

    w = widths["softmax_xent"]
    N, Vl = w["rows"], w["vocab"]
    logits = normal((N, Vl), scale=3.0)
    off = Vl                    # the second of four vocab shards
    labels = jax.random.randint(next(keys), (N,), 0, 4 * Vl, jnp.int32)
    stats, *times["softmax_xent"] = _timed(
        lambda x, y: xent_local_stats_pallas(x, y, off,
                                             interpret=interpret),
        logits, labels)
    want = _ref(local_stats_ref, logits, labels, off, static_argnums=2)
    for name, g, r in zip(("m", "s", "z"), stats, want):
        _check_close(f"softmax_xent.{name}", g, r, KERNEL_TOL)

    w = widths["ssd_scan"]
    B, L, H, P, N = (w["batch"], w["seq"], w["heads"], w["head_dim"],
                     w["state"])
    x = normal((B, L, H, P))
    dt = jax.random.uniform(next(keys), (B, L, H), jnp.float32, 0.01, 0.2)
    A = -jax.random.uniform(next(keys), (H,), jnp.float32, 0.5, 2.0)
    Bm = normal((B, L, 1, N))
    Cm = normal((B, L, 1, N))
    D = normal((H,), jnp.float32)
    (y, hT), *times["ssd_scan"] = _timed(
        lambda *a: ssd_scan_pallas(*a, chunk=w["chunk"],
                                   interpret=interpret),
        x, dt, A, Bm, Cm, D)
    y_ref, h_ref = _ref(ssd_sequential_ref, x, dt, A, Bm, Cm, D)
    _check_close("ssd_scan.y", y, y_ref, KERNEL_TOL)
    _check_close("ssd_scan.h", hT, h_ref, KERNEL_TOL)

    for name, (c, r) in times.items():
        setup(f"kernel {name}: compile {c!r} s, run {r!r} s")
    return times


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_requests(vocab: int, *, n: int = 8, min_prompt: int = 64,
                  max_prompt: int = 512, max_new_tokens: int = 32,
                  seed: int = 0):
    """``n`` requests from ``seed``: prompt lengths are multiples of
    ``min_prompt`` up to ``max_prompt``; generation lengths all differ
    where ``max_new_tokens`` allows."""
    rng = np.random.default_rng(seed)
    lens = rng.choice(np.arange(min_prompt, max_prompt + 1, min_prompt), n)
    gens = rng.permutation(np.linspace(max(1, max_new_tokens // 4),
                                       max_new_tokens, n).astype(int))
    return [(rng.integers(0, vocab, int(p)).astype(np.int32), int(g))
            for p, g in zip(lens, gens)]


def _serve_once(cfg, requests, *, mesh=None, cache="dense",
                shape=None, timeout: float = 900.0, logits_of=None):
    """Compile one serve session, generate twice (the second call runs
    warm), free the session. Returns the tokens, and the first-token logits
    of ``logits_of`` (a prompt) when given."""
    shape = SERVE_SHAPE if shape is None else shape
    t0 = time.perf_counter()
    sess = api.compile(cfg, mode="serve", backend="actors", mesh=mesh,
                       timeout=timeout,
                       **({"cache": "paged"} if cache == "paged" else {}),
                       **shape)
    t1 = time.perf_counter()
    outs = sess.generate(requests)
    t2 = time.perf_counter()
    again = sess.generate(requests)
    t3 = time.perf_counter()
    logits = None
    if logits_of is not None:
        logits = first_token_logits(sess, logits_of)
    sess.close()
    del sess
    gc.collect()
    setup(f"serve {cfg.name} cache={cache}: api.compile {t1 - t0!r} s, "
          f"first generate (jit compiles included) {t2 - t1!r} s, "
          f"second generate {t3 - t2!r} s; peak bytes {peak_bytes()}")
    for a, b in zip(outs, again):
        np.testing.assert_array_equal(a, b, "generate is not repeatable")
    for o, (_, gen) in zip(outs, requests):
        assert len(o) == gen, (len(o), gen)
        assert ((o >= 0) & (o < cfg.vocab_size)).all()
    return outs, logits


def first_token_logits(sess, prompt):
    """The first-token logits of ``prompt`` through the session's compiled
    stage prefill programs (the path ``generate`` admits requests by)."""
    x = jnp.asarray(np.asarray(prompt, np.int32)[None])
    last = jnp.full((1,), len(prompt) - 1, jnp.int32)
    for st in sess.sstaged.stages:
        x, _ = st.prefill(st.params, x, last)
    return np.asarray(x, np.float32)[0, :sess.cfg.vocab_size]


def phase_serve(cfg, requests, *, shape=None, timeout: float = 900.0):
    """Dense, then paged, over the same requests on the default (one-chip)
    mesh; the token ids must be identical."""
    dense, _ = _serve_once(cfg, requests, shape=shape, timeout=timeout)
    paged, _ = _serve_once(cfg, requests, cache="paged", shape=shape,
                           timeout=timeout)
    for i, (a, b) in enumerate(zip(dense, paged)):
        np.testing.assert_array_equal(a, b, f"request {i}: paged != dense")
    setup(f"serve: {len(requests)} requests, "
          f"{sum(len(o) for o in dense)} tokens, paged == dense")
    return dense


#: TP=4 against one chip. bf16 activations round at 2^-8; tensor
#: parallelism reorders every row-parallel partial sum in all layers, so
#: the two first-token logit vectors agree only to a relative L2 error of
#: a few 1e-2 — greedy tokens then diverge once two logits come that close.
TP_LOGITS_REL_L2 = 0.1


def phase_serve_tp(cfg, requests, *, tp: int = 4, shape=None,
                   timeout: float = 900.0):
    """The same requests on a (1, tp) mesh (every stage tensor-parallel
    over ``tp`` chips, dense cache) and on one chip: first-token logits
    within :data:`TP_LOGITS_REL_L2`; the share of identical tokens is
    printed."""
    devs = jax.devices()
    prompt = requests[0][0]
    tp_out, tp_logits = _serve_once(
        cfg, requests, mesh=make_mesh((1, tp), ("data", "model"),
                                      devices=devs[:tp]),
        shape=shape, timeout=timeout, logits_of=prompt)
    one_out, one_logits = _serve_once(
        cfg, requests, mesh=make_mesh((1, 1), ("data", "model"),
                                      devices=devs[:1]),
        shape=shape, timeout=timeout, logits_of=prompt)
    assert np.isfinite(tp_logits).all()
    rel = float(np.linalg.norm(tp_logits - one_logits)
                / np.linalg.norm(one_logits))
    same = sum(int((a == b).sum()) for a, b in zip(tp_out, one_out))
    total = sum(len(a) for a in one_out)
    setup(f"serve tp={tp} vs one chip: first-token logits rel L2 {rel!r}, "
          f"max abs diff {float(np.max(np.abs(tp_logits - one_logits)))!r}, "
          f"identical tokens {same}/{total} = {same / total!r}")
    assert rel <= TP_LOGITS_REL_L2, (rel, TP_LOGITS_REL_L2)
    return rel, same / total


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_graph(width: int, layers: int, batch: int) -> LogicalGraph:
    """``layers`` matmuls of ``width`` x ``width`` (relu between) and a
    softmax-xent head over ``width`` classes."""
    g = LogicalGraph(Placement(("data",), (1,),
                               device_kind=jax.devices()[0].platform))
    h = g.input("x", (batch, width))
    labels = g.input("labels", (batch,), dtype="int32")
    for i in range(layers):
        h = g.matmul(h, g.input(f"w{i}", (width, width)), name=f"mm{i}")
        if i < layers - 1:
            h = g.unary(h, "relu", name=f"relu{i}")
    g.softmax_xent(h, labels, name="loss")
    return g


#: Tolerance when the pipeline and the monolithic program do not agree
#: bitwise, relative to the largest reference magnitude of each tensor. The
#: pipeline runs each stage as its own XLA program, the reference runs one
#: program, so the compiler may tile and fuse the fp32 reductions (xent row
#: sums, the batch contraction of each weight gradient, the norm) differently
#: and round differently in the last bits; AdamW's m / (sqrt(v) + eps) maps
#: such a gradient difference into the params without amplifying it beyond
#: lr * |dg| / eps, far below this bound at these magnitudes.
TRAIN_REL_TOL = 1e-4


def _rel_diff(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    assert a.shape == b.shape, (a.shape, b.shape)
    scale = max(float(np.max(np.abs(b))), 1e-30)
    return float(np.max(np.abs(a - b))) / scale


def compare_train(a, b, batch, steps: int):
    """Step both sessions ``steps`` times on ``batch`` and compare what
    ``api.assert_sessions_match`` compares (loss, grads, params, AdamW
    moments and step). Returns the largest relative difference; bitwise
    agreement returns 0.0, anything else must stay within
    :data:`TRAIN_REL_TOL`."""
    worst = 0.0
    for k in range(steps):
        t0 = time.perf_counter()
        sa = a.step(**batch)
        t1 = time.perf_counter()
        sb = b.step(**batch)
        t2 = time.perf_counter()
        setup(f"train step {k}: pipeline {t1 - t0!r} s, monolithic "
              f"{t2 - t1!r} s (step 0 includes jit compiles)")
        assert np.isfinite(float(sa.loss)), sa.loss
        pairs = [("loss", sa.loss, sb.loss)]
        pairs += [(f"grad {n}", sa.grads[n], sb.grads[n]) for n in sb.grads]
        pairs += [(f"param {n}", sa.params[n], sb.params[n])
                  for n in sb.params]
        oa, ob = a.opt_state, b.opt_state
        assert int(oa.step) == int(ob.step) == k + 1
        pairs += [(f"mu {n}", oa.mu[n], ob.mu[n]) for n in ob.mu]
        pairs += [(f"nu {n}", oa.nu[n], ob.nu[n]) for n in ob.nu]
        diffs = {name: _rel_diff(x, y) for name, x, y in pairs}
        name, d = max(diffs.items(), key=lambda kv: kv[1])
        worst = max(worst, d)
        setup(f"train step {k}: loss {float(sa.loss)!r}; largest relative "
              f"diff vs monolithic {d!r} ({name}); bitwise "
              f"{all(v == 0.0 for v in diffs.values())}")
        assert d <= TRAIN_REL_TOL, (name, d, TRAIN_REL_TOL)
    return worst


def phase_train(*, width: int, layers: int, batch: int, stages: int,
                microbatches: int, steps: int, stage_meshes=None,
                mono_mesh=None, seed: int = 0, timeout: float = 900.0):
    """The 1F1B actor pipeline (``stages`` stages, optionally one mesh per
    stage) against the monolithic program on ``mono_mesh``."""
    g = train_graph(width, layers, batch)
    rng = np.random.default_rng(seed)
    params = {f"w{i}": (rng.normal(size=(width, width)) / np.sqrt(width)
                        ).astype(np.float32) for i in range(layers)}
    data = {"x": rng.normal(size=(batch, width)).astype(np.float32),
            "labels": rng.integers(0, width, batch).astype(np.int32)}
    opt = OptimizerSpec.adamw(grad_clip=1.0)
    t0 = time.perf_counter()
    sess = api.compile(g, mode="train", stages=stages,
                       num_microbatches=microbatches, params=params,
                       optimizer=opt, stage_meshes=stage_meshes,
                       timeout=timeout)
    mono = api.compile(g, mode="train", backend="monolithic", params=params,
                       num_microbatches=microbatches, optimizer=opt,
                       mesh=mono_mesh)
    setup(f"train: api.compile x2 {time.perf_counter() - t0!r} s")
    try:
        worst = compare_train(sess, mono, data, steps)
    finally:
        sess.close()
        mono.close()
    setup(f"train: {steps} steps, largest relative diff {worst!r}; "
          f"peak bytes {peak_bytes()}")
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip paths (TP=4 serve, "
                         "stage-mesh 1F1B) against one chip")
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found {devs[0].platform!r} devices",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 1
    enable_compile_cache()
    setup(f"device {devs[0].device_kind!r} x{len(devs)}, jax "
          f"{jax.__version__}, compile cache "
          f"{jax.config.jax_compilation_cache_dir!r}")

    cfg = get_config("qwen3-1.7b")
    requests = make_requests(cfg.vocab_size,
                             max_prompt=SERVE_SHAPE["max_prompt_len"],
                             max_new_tokens=SERVE_SHAPE["max_new_tokens"])
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_kernels()
        setup(f"after kernels: peak bytes {peak_bytes()}")
        phase_serve(cfg, requests)
        phase_train(**TRAIN_WIDTHS)
    else:
        phase_serve_tp(cfg, requests, tp=args.chips)
        one = make_mesh((1,), ("data",), devices=devs[:1])
        phase_train(**TRAIN_WIDTHS,
                    stage_meshes=[make_mesh((1,), ("data",), devices=[d])
                                  for d in devs[:TRAIN_WIDTHS["stages"]]],
                    mono_mesh=one)
    setup(f"all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
