"""Multi-device suite: continuous-batching serve pipeline on real meshes.

Two placements a single-device test cannot reach:

* (1, 2) model-parallel: the KV cache is sequence-sharded over the model
  axis inside every stage (flash-decode partials combined with pmax/psum),
  and the stage-boundary hidden is replicated;
* (2, 1) data-parallel: the group cache is batch-sharded over the data axis
  while admission prefills (batch 1) run replicated and are scattered into
  the sharded group cache slot.

Both must be token-identical to the monolithic make_serve_step loop.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "src"))

import numpy as np

PROMPT_LEN = 8
CACHE_LEN = 16


def reference(cfg, mesh, params, prompts, gens):
    import jax
    import jax.numpy as jnp

    from repro.train.steps import greedy_from_logits, make_serve_step

    ss = make_serve_step(cfg, mesh, cache_len=CACHE_LEN)
    tokens = jnp.asarray(np.stack(prompts), jnp.int32)
    h_last, caches = ss.prefill_fn(params, {"tokens": tokens})
    tok = greedy_from_logits(ss.logits_fn(params, h_last), cfg.vocab_size)
    rows = [np.asarray(tok)]
    pos = jnp.full((len(prompts),), PROMPT_LEN, jnp.int32)
    for _ in range(max(gens) - 1):
        logits, caches = ss.decode_fn(params, caches, tok, pos)
        tok = greedy_from_logits(logits, cfg.vocab_size)
        rows.append(np.asarray(tok))
        pos = pos + 1
    mat = np.stack(rows, 1)
    return [mat[i, :g] for i, g in enumerate(gens)]


def run_mesh(mesh_shape, group_size, num_groups, gens, label):
    import jax

    from repro import api
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.model_zoo import build_model
    from repro.train.steps import plan_from_mesh

    cfg = get_config("qwen2.5-3b").reduced()
    cfg = dataclasses.replace(cfg, vocab_size=1000)   # padded vocab
    mesh = make_mesh(mesh_shape, ("data", "model"))
    params = build_model(cfg, plan_from_mesh(mesh)).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (PROMPT_LEN,)).astype(np.int32)
               for _ in gens]
    ref = reference(cfg, mesh, params, prompts, gens)

    sess = api.compile(cfg, mode="serve", backend="actors", stages=2,
                       params=params, mesh=mesh, num_groups=num_groups,
                       group_size=group_size, max_prompt_len=PROMPT_LEN,
                       max_new_tokens=max(gens), cache_len=CACHE_LEN)
    outs = sess.generate(list(zip(prompts, gens)))
    for i, (got, want) in enumerate(zip(outs, ref)):
        assert np.array_equal(got, want), (
            f"{label} request {i}: {got} != {want}")
    assert all((o < cfg.vocab_size).all() for o in outs)
    if num_groups * group_size < len(gens):
        assert sess.last_stats["admitted_mid_flight"] >= 1, label
    print(f"{label}: {sess.last_stats['tokens']} tokens token-identical "
          f"({sess.last_stats['admitted_mid_flight']} admitted mid-flight)")


def main():
    # model-parallel: seq-sharded KV cache, 3 requests through 2 slots
    run_mesh((1, 2), group_size=1, num_groups=2, gens=[2, 4, 3],
             label="mp(1x2)")
    # data-parallel: batch-sharded group cache, replicated admission prefill
    # (4 requests so the reference prefill batch divides the data axis)
    run_mesh((2, 1), group_size=2, num_groups=1, gens=[2, 4, 3, 1],
             label="dp(2x1)")


if __name__ == "__main__":
    main()
    print("ALL-OK")
