"""``chip_smoke.py``'s phases at reduced size on the CPU, and the api.compile
guards the chip bring-up rests on (Auto-axis meshes, one process per chip).

The script itself has no CPU mode: here its phase functions run with the
kernels in interpret mode and ``cfg.reduced()`` shapes, and ``main()`` must
refuse a CPU backend before any phase.
"""
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro import api
from repro.configs.registry import get_config

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMALL_KERNELS = {
    "flash_attention": dict(batch=1, seq=40, heads=4, kv_heads=2,
                            head_dim=16),
    "flash_decode": dict(batch=2, cache=48, heads=4, kv_heads=2,
                         head_dim=16),
    "flash_decode_paged": dict(cache=48, page_len=8, layers=2, heads=4,
                               kv_heads=2, head_dim=16),
    "softmax_xent": dict(rows=16, vocab=300),
    "ssd_scan": dict(batch=1, seq=48, heads=2, head_dim=8, state=16,
                     chunk=16),
}

SMALL_SERVE = dict(stages=2, num_groups=2, group_size=2, max_prompt_len=16,
                   max_new_tokens=5, cache_len=32)


def test_kernel_phase_interpret(smoke):
    times = smoke.phase_kernels(SMALL_KERNELS, interpret=True)
    assert set(times) == set(SMALL_KERNELS)


def test_serve_phase_reduced(smoke):
    cfg = get_config("qwen3-1.7b").reduced()
    reqs = smoke.make_requests(cfg.vocab_size, n=4, min_prompt=4,
                               max_prompt=16, max_new_tokens=5)
    assert len({g for _, g in reqs}) == len(reqs)      # unequal lengths
    outs = smoke.phase_serve(cfg, reqs, shape=SMALL_SERVE)
    assert [len(o) for o in outs] == [g for _, g in reqs]


def test_serve_phase_reduced_in_place(smoke, monkeypatch):
    """With the paged flash-decode in interpret mode (as on the TPU) a
    paged session compiled to gather must match the dense tokens exactly,
    and one that reads the slabs in place, fed its tokens, keeps its
    logits within the limit."""
    import functools

    from repro.core import lowering
    from repro.kernels.flash_decode.kernel import flash_decode_paged_pallas
    monkeypatch.setattr(lowering, "paged_decode_kernel", lambda platform:
                        functools.partial(flash_decode_paged_pallas,
                                          interpret=True))
    seen = []
    serve_once = smoke._serve_once

    def spy(*a, **k):
        out = serve_once(*a, **k)
        seen.append(out[2].get("decode_fires_paged"))
        return out
    monkeypatch.setattr(smoke, "_serve_once", spy)
    cfg = get_config("qwen3-1.7b").reduced()
    reqs = smoke.make_requests(cfg.vocab_size, n=4, min_prompt=4,
                               max_prompt=16, max_new_tokens=5)
    outs = smoke.phase_serve(cfg, reqs, shape=SMALL_SERVE)
    assert [len(o) for o in outs] == [g for _, g in reqs]
    assert seen[0] is None and seen[1] == 0 and seen[2] > 0
    assert lowering.paged_decode_kernel("cpu") is not None   # restored


def test_train_phase_reduced(smoke):
    worst = smoke.phase_train(width=32, layers=4, batch=16, stages=4,
                              microbatches=4, steps=2)
    assert worst == 0.0          # bitwise on the CPU


def test_main_refuses_cpu_before_any_phase(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and "[setup]" not in out


def test_compile_rebuilds_explicit_mesh_as_auto():
    """A caller's Explicit-axis mesh (jax.make_mesh's default) serves:
    api.compile rebuilds it with Auto axes at its one entry."""
    cfg = get_config("qwen3-1.7b").reduced()
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Explicit,) * 2
    sess = api.compile(cfg, mode="serve", backend="actors", mesh=mesh,
                       **SMALL_SERVE)
    try:
        assert sess.mesh.axis_types == (AxisType.Auto,) * 2
        assert sess.mesh.devices.tolist() == mesh.devices.tolist()
        assert sess.mesh.axis_names == mesh.axis_names
        rng = np.random.default_rng(0)
        out = sess.generate([(rng.integers(0, cfg.vocab_size, 8), 3)])
        assert len(out[0]) == 3
    finally:
        sess.close()


def test_contract_line_shape(smoke, monkeypatch, capsys):
    """With every phase stubbed out, main's last line is exactly the
    contract object, naming the device as JAX reports it."""
    class Dev:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0

        def memory_stats(self):
            return {}

    monkeypatch.setattr(smoke.jax, "devices", lambda: [Dev()])
    for name in ("phase_kernels", "phase_serve", "phase_train",
                 "enable_compile_cache"):
        monkeypatch.setattr(smoke, name, lambda *a, **k: None)
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
