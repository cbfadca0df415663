"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 50 --batch 8 --seq 128

``--smoke`` uses the reduced config (CPU-scale); without it, the full config
is used (real cluster). The data pipeline is the actor-runtime prefetcher
(paper §6.1); checkpointing every ``--ckpt-every`` steps.
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config for CPU")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--zero", action="store_true", default=True)
    ap.add_argument("--no-zero", dest="zero", action="store_false")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="checkpoints/run")
    ap.add_argument("--data-buffers", type=int, default=2)
    ap.add_argument("--mesh", default="1x1",
                    help="data x model, e.g. 2x4 (needs that many devices)")
    args = ap.parse_args()

    import jax

    from repro.configs.registry import get_config
    from repro.data.pipeline import ActorDataPipeline, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.xla_env import enable_compile_cache
    from repro.optim.adamw import AdamWConfig
    from repro.train.checkpoint import save_checkpoint
    from repro.train.steps import make_train_step

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    d_, m_ = (int(v) for v in args.mesh.split("x"))
    mesh = make_mesh((d_, m_), ("data", "model"))

    ts = make_train_step(cfg, mesh, optimizer=AdamWConfig(lr=args.lr),
                         zero=args.zero)
    params = ts.init_params(jax.random.PRNGKey(0))
    # place params according to their (model) specs
    params = jax.tree.map(
        lambda p, s: jax.device_put(p, jax.sharding.NamedSharding(mesh, s)),
        params, ts.model_param_specs,
        is_leaf=lambda x: not isinstance(x, dict) and not isinstance(x, list))
    if ts.zero:
        params = ts.shard_params_fn(params)   # flat fp32 master shards
    opt_state = ts.init_opt(params)

    src = SyntheticLM(cfg.vocab_size, args.batch, args.seq)
    pipe = ActorDataPipeline(src, num_batches=args.steps,
                             buffers=args.data_buffers)

    t0 = time.time()
    losses = []
    for step, tokens in enumerate(pipe):
        batch = {"tokens": tokens}
        params, opt_state, metrics = ts.step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % 10 == 0 or step == args.steps - 1:
            dt = time.time() - t0
            tok_s = (step + 1) * args.batch * args.seq / dt
            print(f"step {step:4d}  loss {loss:.4f}  "
                  f"grad_norm {float(metrics['grad_norm']):.3f}  "
                  f"{tok_s:,.0f} tok/s")
        if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            full = ts.gather_params_fn(params) if ts.zero else params
            save_checkpoint(args.ckpt_dir, {"params": full}, step=step + 1,
                            meta={"arch": cfg.name})
            print(f"  checkpoint @ step {step + 1} -> {args.ckpt_dir}")
    print(f"first loss {losses[0]:.4f} -> last {losses[-1]:.4f} "
          f"({'improved' if losses[-1] < losses[0] else 'NOT improved'})")
    assert losses[-1] < losses[0], "training did not reduce the loss"


if __name__ == "__main__":
    main()
