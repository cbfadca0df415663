"""Serving driver: continuous-batching pipelined decode on the actor runtime.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --requests 6 --prompt-len 32 --gen 16 --backend actors --stages 2

Token-frontend archs go through ``repro.api.compile(cfg, mode="serve")``:
requests with differing generation lengths are packed into decode slots,
finished requests retire and queued ones are admitted mid-flight, and the
stage actors overlap across request groups. Embed-frontend / encoder-decoder
archs (pixtral, whisper) fall back to the classic monolithic batched loop
(``--classic`` forces it for any arch).
"""
from __future__ import annotations

import argparse
import time


def classic_loop(cfg, args, mesh):
    """The pre-pipeline serve loop: one batched prefill + greedy decode.

    First-token logits go through ``ServeStep.logits_fn`` — the same
    jitted/shard-mapped head as the decode step — and greedy selection masks
    the padded vocab columns, so emitted ids are always < cfg.vocab_size.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model_zoo import build_model
    from repro.train.steps import (greedy_from_logits, make_serve_step,
                                   plan_from_mesh)

    m_ = mesh.devices.shape[1]
    cache_len = args.cache_len or (args.prompt_len + args.gen + 8)
    cache_len = ((cache_len + m_ - 1) // m_) * m_

    ss = make_serve_step(cfg, mesh, cache_len=cache_len)
    bundle = build_model(cfg, plan_from_mesh(mesh))
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    batch = {}
    if cfg.embed_frontend and not cfg.encoder_decoder:
        batch["embeds"] = jnp.asarray(rng.normal(
            size=(args.batch, args.prompt_len, cfg.d_model)).astype(np.float32))
    else:
        batch["tokens"] = jnp.asarray(rng.integers(
            0, cfg.vocab_size, size=(args.batch, args.prompt_len)), jnp.int32)
    if cfg.encoder_decoder:
        batch["enc_embeds"] = jnp.asarray(rng.normal(
            size=(args.batch, cfg.encoder_seq, cfg.d_model)).astype(np.float32))

    t0 = time.time()
    h_last, caches = ss.prefill_fn(params, batch)
    h_last.block_until_ready()
    print(f"prefill {args.batch}x{args.prompt_len}: {time.time()-t0:.2f}s")

    # greedy decode from the last prefill hidden, through the decode head
    tok = greedy_from_logits(ss.logits_fn(params, h_last), cfg.vocab_size)
    generated = [np.asarray(tok)]
    pos = jnp.full((args.batch,), args.prompt_len, jnp.int32)
    t0 = time.time()
    for _ in range(args.gen):
        logits, caches = ss.decode_fn(params, caches, tok, pos)
        tok = greedy_from_logits(logits, cfg.vocab_size)
        generated.append(np.asarray(tok))
        pos = pos + 1
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"decode {args.gen} steps: {dt:.2f}s "
          f"({args.gen*args.batch/dt:.1f} tok/s)")
    gen = np.stack(generated, axis=1)
    print("generated ids (first row):", gen[0][:16])
    assert gen.shape == (args.batch, args.gen + 1)
    assert (gen >= 0).all() and (gen < cfg.vocab_size).all()
    print("serve ok (classic loop)")


def continuous_batching(cfg, args, mesh):
    import numpy as np

    from repro import api

    sess = api.compile(cfg, mode="serve", backend=args.backend,
                       stages=args.stages, mesh=mesh,
                       num_groups=args.groups, group_size=args.slots,
                       max_prompt_len=args.prompt_len,
                       max_new_tokens=args.gen,
                       cache_len=args.cache_len or None)
    print(sess.describe())

    rng = np.random.default_rng(0)
    requests = []
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, (args.prompt_len,))
        gen = max(1, args.gen - (i % max(1, args.gen // 2)))  # unequal lengths
        requests.append((prompt.astype(np.int32), gen))

    outs = sess.generate(requests)
    stats = sess.last_stats
    print(f"{args.requests} requests, {stats['tokens']} tokens in "
          f"{stats['rounds']} rounds / {stats['wall_s']:.2f}s "
          f"({stats['tok_per_s']:.1f} tok/s, "
          f"{stats['admitted_mid_flight']} admitted mid-flight)")
    print("generated ids (first request):", outs[0][:16])
    assert all(len(o) == g for o, (_, g) in zip(outs, requests))
    assert all((o >= 0).all() and (o < cfg.vocab_size).all() for o in outs)
    print("serve ok (continuous batching)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--backend", default="actors",
                    choices=("actors", "monolithic"))
    ap.add_argument("--classic", action="store_true",
                    help="force the monolithic batched prefill+decode loop")
    ap.add_argument("--stages", type=int, default=None)
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--slots", type=int, default=2,
                    help="decode slots per request group")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=4,
                    help="batch size of the classic loop")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=0)
    ap.add_argument("--mesh", default="1x1")
    args = ap.parse_args()

    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.xla_env import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    d_, m_ = (int(v) for v in args.mesh.split("x"))
    mesh = make_mesh((d_, m_), ("data", "model"))

    if args.classic or cfg.embed_frontend or cfg.encoder_decoder:
        classic_loop(cfg, args, mesh)
    else:
        continuous_batching(cfg, args, mesh)


if __name__ == "__main__":
    main()
