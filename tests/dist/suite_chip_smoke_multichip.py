"""Multi-device suite: ``chip_smoke.py --chips 4``'s phases at reduced size.

The four-chip bring-up paths on four placeholder CPU devices:

* serve — qwen3 (reduced) on a (1, 4) mesh, every stage tensor-parallel
  with the dense cache, against the same requests on one device: the
  first-token logits within the script's tolerance;
* train — the 1F1B graph with one single-device mesh per stage (stages on
  devices 0..3, never all on device 0) against the monolithic program on
  device 0, bitwise here.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import importlib.util
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main():
    import jax

    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh

    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    devs = jax.devices()
    assert len(devs) == 4
    cfg = get_config("qwen3-1.7b").reduced()
    reqs = smoke.make_requests(cfg.vocab_size, n=4, min_prompt=4,
                               max_prompt=16, max_new_tokens=5)
    shape = dict(stages=2, num_groups=2, group_size=2, max_prompt_len=16,
                 max_new_tokens=5, cache_len=32)
    rel, same = smoke.phase_serve_tp(cfg, reqs, tp=4, shape=shape)
    print(f"serve tp=4: logits rel L2 {rel:.2e}, identical tokens {same:.2f}")

    meshes = [make_mesh((1,), ("data",), devices=[d]) for d in devs]
    used = {m.devices.ravel()[0].id for m in meshes}
    assert used == {0, 1, 2, 3}
    worst = smoke.phase_train(width=32, layers=4, batch=16, stages=4,
                              microbatches=4, steps=2, stage_meshes=meshes,
                              mono_mesh=make_mesh((1,), ("data",),
                                                  devices=devs[:1]))
    assert worst == 0.0, worst
    print("train stage meshes on 4 devices: bitwise vs monolithic")
    print("ALL-OK")


if __name__ == "__main__":
    main()
