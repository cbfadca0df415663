"""End-to-end example: train a language model with the actor data pipeline,
ZeRO/FSDP optimizer sharding, and checkpointing.

CPU demo (a ~15M-param qwen3-family model, loss must drop):
    python examples/train_lm.py

~100M model, a few hundred steps (hours on 1 CPU core; minutes on devices):
    python examples/train_lm.py --d-model 512 --layers 8 \
        --steps 300 --batch 8 --seq 256
"""
try:
    from examples import _bootstrap  # noqa: F401  (python -m examples.train_lm)
except ImportError:
    import _bootstrap  # noqa: F401  (python examples/train_lm.py)

import argparse
import dataclasses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    import jax

    from repro.configs.registry import get_config
    from repro.data.pipeline import ActorDataPipeline, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.train.checkpoint import load_checkpoint, save_checkpoint
    from repro.train.steps import make_train_step

    cfg = dataclasses.replace(
        get_config("qwen3-1.7b").reduced(),
        num_layers=args.layers, d_model=args.d_model,
        d_ff=args.d_model * 3, vocab_size=4096)
    n_params = cfg.param_count()
    print(f"model: {n_params/1e6:.1f}M params "
          f"({cfg.num_layers}L d={cfg.d_model})")

    mesh = make_mesh((1, 1), ("data", "model"))
    ts = make_train_step(cfg, mesh, optimizer=AdamWConfig(lr=3e-4), zero=True)
    params = ts.init_params(jax.random.PRNGKey(0))
    masters = ts.shard_params_fn(params)
    opt = ts.init_opt(masters)

    pipe = ActorDataPipeline(SyntheticLM(cfg.vocab_size, args.batch, args.seq),
                             num_batches=args.steps, buffers=2)
    losses = []
    for step, tokens in enumerate(pipe):
        masters, opt, metrics = ts.step_fn(masters, opt, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {losses[-1]:.4f}  "
                  f"grad_norm {float(metrics['grad_norm']):.3f}")

    assert losses[-1] < losses[0], "loss did not improve"
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f}  OK")

    if args.ckpt:
        full = ts.gather_params_fn(masters)
        save_checkpoint(args.ckpt, {"params": full}, step=args.steps)
        restored, step = load_checkpoint(args.ckpt, {"params": full})
        print(f"checkpoint round-trip at step {step}: OK")


if __name__ == "__main__":
    main()
