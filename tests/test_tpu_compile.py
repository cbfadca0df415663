"""Compile the Pallas kernels, the paged cache's scatter programs,
serve stage programs and the grouped expert form for a described TPU v5e
(no chip needed).

The TPU compiler is installed with jax; ``get_topology_desc`` describes a
``v5e:2x2`` host without attaching it, so ``lower(...).compile()`` raises
exactly what the chip's compiler would (misaligned blocks, VMEM overflow,
unsupported ops). Each kernel is compiled with ``interpret=False`` at the
widths ``chip_smoke.py`` runs, and must contain its Mosaic custom call.

The topology is described inside a module fixture, never at import time:
loading the TPU library takes a process-wide lock, and under pytest-xdist
every worker imports this file.
"""
import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_decode.kernel import (flash_decode_paged_pallas,
                                               flash_decode_pallas)
from repro.kernels.softmax_xent.kernel import xent_local_stats_pallas
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_qwen3_prefill(one_chip):
    bf = jnp.bfloat16
    _compile(functools.partial(flash_attention_pallas, causal=True),
             ((1, 2048, 16, 128), bf), ((1, 2048, 8, 128), bf),
             ((1, 2048, 8, 128), bf), sharding=one_chip)


def test_flash_decode_compiles_qwen3_cache(one_chip):
    bf = jnp.bfloat16

    def fn(q, k, v, pos):
        return flash_decode_pallas(q, k, v, cur_pos=pos)

    _compile(fn, ((4, 16, 128), bf), ((4, 4096, 8, 128), bf),
             ((4, 4096, 8, 128), bf), ((4,), jnp.int32), sharding=one_chip)


def test_flash_decode_paged_compiles_qwen3_slabs(one_chip):
    """The paged flash-decode at the qwen3-1.7b cell's sizes (a stage's 14
    layers of 1224 pages of 16 positions, 8 KV heads of 128, 4 slots of
    153 table entries) reads the slabs as they are: the row view is a
    bitcast, and no slab is copied."""
    bf = jnp.bfloat16
    slab = (14, 1224, 16, 8, 128)
    compiled = _compile(flash_decode_paged_pallas, ((4, 16, 128), bf),
                        (slab, bf), (slab, bf), ((4, 153), jnp.int32),
                        ((4,), jnp.int32), ((), jnp.int32),
                        sharding=one_chip)
    text = compiled.as_text()
    assert not re.search(r"bf16\[%s\][^=]*copy\(" % ",".join(map(str, slab)),
                         text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_softmax_xent_compiles_vocab_slice(one_chip):
    def fn(logits, labels, off):
        return xent_local_stats_pallas(logits, labels, off)

    _compile(fn, ((1024, 37984), jnp.bfloat16), ((1024,), jnp.int32),
             ((), jnp.int32), sharding=one_chip)


def test_ssd_scan_compiles_mamba2_370m(one_chip):
    bf, f32 = jnp.bfloat16, jnp.float32
    _compile(functools.partial(ssd_scan_pallas, chunk=128),
             ((1, 2048, 32, 64), bf), ((1, 2048, 32), f32), ((32,), f32),
             ((1, 2048, 1, 128), bf), ((1, 2048, 1, 128), bf), ((32,), f32),
             sharding=one_chip)


@pytest.mark.parametrize("kind", ["decode", "prefill", "chunk",
                                  "decode_new"])
def test_paged_scatter_updates_slabs_in_place(one_chip, kind):
    """At the qwen3-1.7b benchmark cell's sizes (one stage of 14 layers,
    2 x 4 slots, cache 2448 in pages of 16), each scatter aliases the
    donated slabs to its output and copies no whole slab (``decode_new``:
    the scatter of an in-place decode's new positions alone)."""
    from repro.serve.paged_cache import PagedCacheSpec, _build_paged_ops

    P, B, L, KV, HD = 14, 4, 2448, 8, 128
    spec = PagedCacheSpec(page_len=16, num_pages=1224, max_requests=8,
                          pages_per_req=153)
    fns = _build_paged_ops(spec, B, L)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slab_shape = (P, spec.num_pages, spec.page_len, KV, HD)
    slabs = {"prologue": [], "body": [{"k": sds(slab_shape),
                                       "v": sds(slab_shape)}]}
    vec = sds((B,), jnp.int32)
    rows = sds((B, spec.pages_per_req), jnp.int32)
    window = {"prologue": [], "body": [{"k": sds((P, B, L, KV, HD)),
                                        "v": sds((P, B, L, KV, HD))}]}
    if kind == "decode":
        lowered = fns["scatter_decode"].lower(slabs, rows, vec, vec, window)
    elif kind == "decode_new":
        new = {"prologue": [], "body": [{"k": sds((P, B, KV, HD)),
                                         "v": sds((P, B, KV, HD))}]}
        lowered = fns["scatter_decode_new"].lower(slabs, rows, vec, vec, new)
    elif kind == "prefill":
        slot = {"prologue": [], "body": [{"k": sds((P, 1, L, KV, HD)),
                                          "v": sds((P, 1, L, KV, HD))}]}
        lowered = fns["scatter_prefill"].lower(
            slabs, sds((spec.pages_per_req,), jnp.int32),
            sds((), jnp.int32), slot)
    else:
        lowered = fns["scatter_chunk"](128).lower(slabs, rows, vec, vec, vec,
                                                  window)
    text = lowered.compile().as_text()
    assert "input_output_alias={ {0}: (0, {}" in text
    assert "{1}: (1, {}" in text
    slab_copy = r"bf16\[%s\][^=]*copy\(" % ",".join(map(str, slab_shape))
    assert not re.search(slab_copy, text)


def test_deepseek_stage_decode_compiles_at_published_widths(one_chip,
                                                            monkeypatch):
    """DeepSeek-V2-Lite at published widths with 16 of its 64 routed
    experts held (the benchmark cell's cut: 2 stages, 2 x 8 slots, cache
    1936): both stage decode programs compile for a v5e chip, and the
    paged decode scatter aliases the donated latent slabs (``c``, ``kpe``)
    and copies none."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_config
    from repro.core.lowering import lower_serve_stages
    from repro.models.model_zoo import build_model
    from repro.models.common import MeshPlan
    from repro.serve.paged_cache import PagedCacheSpec, _build_paged_ops

    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"),
                              experts_held=16)
    B, L = 8, 1936
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(build_model(cfg, MeshPlan.single_device()).init,
                       jax.random.PRNGKey(0)))
    # a described device holds no arrays: placing the params gives shapes
    monkeypatch.setattr(jax, "device_put", lambda x, s: jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        x, s))
    sstaged = lower_serve_stages(cfg, mesh, params, num_stages=2,
                                 cache_len=L, max_prompt_len=1536,
                                 group_size=B)
    spec = PagedCacheSpec(page_len=16, num_pages=2 * B * L // 16,
                          max_requests=2 * B, pages_per_req=L // 16)
    fns = _build_paged_ops(spec, B, L)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep)
    for st in sstaged.stages:
        assert st.moe
        assert st.decode_slabs is None      # the latent window is gathered
        window = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            jax.eval_shape(st.init_caches, tok))
        xin = tok if st.first else jax.ShapeDtypeStruct(
            (B, 1, cfg.d_model), jnp.bfloat16, sharding=rep)
        st.decode.lower(st.params, window, xin, tok).compile()
        slabs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                (a.shape[0], spec.num_pages, 16) + a.shape[3:]
                if a.ndim == 4 else (spec.num_pages, 16) + a.shape[2:],
                a.dtype, sharding=rep), window)
        rows = jax.ShapeDtypeStruct((B, spec.pages_per_req), jnp.int32,
                                    sharding=rep)
        text = fns["scatter_decode"].lower(slabs, rows, tok, tok,
                                           window).compile().as_text()
        for i, slab in enumerate(jax.tree.leaves(slabs)):
            assert "{%d}: (%d, {}" % (i, i) in text
            shape = ",".join(map(str, slab.shape))
            assert not re.search(r"bf16\[%s\][^=]*copy\(" % shape, text)


def test_qwen3_stage_decode_reads_the_unexpanded_window(one_chip,
                                                        monkeypatch):
    """qwen3-1.7b at the benchmark cell's sizes (2 stages, 4 slots, cache
    2448; 16 query heads over 8 KV heads of 128): both stage decode
    programs compile for a v5e chip, and grouped-query decode attention
    contracts against the cache as stored. No KV-head-expanded window
    (``[4,2448,8,2,128]`` at any dtype) and no float32 copy of a window
    appears anywhere in the optimized programs."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_config
    from repro.core.lowering import lower_serve_stages
    from repro.models.common import MeshPlan
    from repro.models.model_zoo import build_model

    cfg = get_config("qwen3-1.7b")
    B, L = 4, 2448
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(build_model(cfg, MeshPlan.single_device()).init,
                       jax.random.PRNGKey(0)))
    # a described device holds no arrays: placing the params gives shapes
    monkeypatch.setattr(jax, "device_put", lambda x, s: jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        x, s))
    sstaged = lower_serve_stages(cfg, mesh, params, num_stages=2,
                                 cache_len=L, max_prompt_len=2048,
                                 group_size=B)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep)
    expanded = re.compile(r"\w+\[4,2448,8,2,128\]|f32\[4,2448,(8|16),128\]")
    for st in sstaged.stages:
        window = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            jax.eval_shape(st.init_caches, tok))
        assert {a.shape[1:] for a in jax.tree.leaves(window)
                if a.ndim == 5} == {(B, L, 8, 128)}
        xin = tok if st.first else jax.ShapeDtypeStruct(
            (B, 1, cfg.d_model), jnp.bfloat16, sharding=rep)
        text = st.decode.lower(st.params, window, xin,
                               tok).compile().as_text()
        assert not expanded.search(text), expanded.search(text).group(0)


def test_qwen3_paged_stage_decode_reads_slabs_in_place(one_chip,
                                                      monkeypatch):
    """qwen3-1.7b at the benchmark cell's sizes (2 stages, 4 slots, cache
    2448 in 153 pages of 16, a pool of 1224 pages): on a v5e chip each
    stage has an in-place paged decode program, which compiles with the
    paged flash-decode's Mosaic call inside ``local_decode_s{n}``. No op
    or output has a slot group's window shape (``[4,2448,8,128]``,
    stacked or not): nothing gathers a window and nothing returns one."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_config
    from repro.core.lowering import lower_serve_stages
    from repro.models.common import MeshPlan
    from repro.models.model_zoo import build_model

    cfg = get_config("qwen3-1.7b")
    B, L, pages = 4, 2448, 1224
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(build_model(cfg, MeshPlan.single_device()).init,
                       jax.random.PRNGKey(0)))
    # a described device holds no arrays: placing the params gives shapes
    monkeypatch.setattr(jax, "device_put", lambda x, s: jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        x, s))
    sstaged = lower_serve_stages(cfg, mesh, params, num_stages=2,
                                 cache_len=L, max_prompt_len=2048,
                                 group_size=B)
    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep)
    rows = jax.ShapeDtypeStruct((B, L // 16), jnp.int32, sharding=rep)
    window = re.compile(r"\w+\[(\d+,)*4,2448,8,128\]")
    for st in sstaged.stages:
        periods = st.units[1] - st.units[0]    # no prologue: one a layer
        slab = jax.ShapeDtypeStruct((periods, pages, 16, 8, 128),
                                    jnp.bfloat16, sharding=rep)
        slabs = {"prologue": [], "body": [{"k": slab, "v": slab}]}
        xin = vec if st.first else jax.ShapeDtypeStruct(
            (B, 1, cfg.d_model), jnp.bfloat16, sharding=rep)
        lowered = st.decode_slabs.lower(st.params, slabs, rows, vec, xin,
                                        vec)
        text = lowered.compile().as_text()
        assert text.startswith(f"HloModule jit_local_decode_s{st.index}")
        assert "tpu_custom_call" in text
        assert not window.search(text), window.search(text).group(0)
        new = jax.tree.leaves(lowered.out_info[1])
        assert [a.shape for a in new] == [(periods, B, 8, 128)] * 2


def test_serve_stages_share_one_placed_body(one_chip, monkeypatch):
    """On a v5e:2x2 mesh (2-way data, 2-way model) the serve stages of a
    DeepSeek-shaped stack (MLA, experts split over the model axis) share
    one placement of the stacked body: it is placed once, both stages
    hold the very same leaves, and both stage decode programs compile
    against them."""
    import dataclasses

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_config
    from repro.core.lowering import lower_serve_stages
    from repro.models import transformer as T
    from repro.models.common import MeshPlan
    from repro.models.model_zoo import build_model

    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").reduced(),
                              num_layers=5)
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    mesh = Mesh(np.array(devices).reshape(2, 2), ("data", "model"))
    params = jax.eval_shape(build_model(cfg, MeshPlan.single_device()).init,
                            jax.random.PRNGKey(0))
    placed = []

    def put(x, s):
        out = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            x, s)
        placed.append(jax.tree.leaves(out))
        return out

    monkeypatch.setattr(jax, "device_put", put)
    B, L = 4, 64
    sstaged = lower_serve_stages(cfg, mesh, params, num_stages=2,
                                 cache_len=L, max_prompt_len=32,
                                 group_size=B)
    st0, st1 = sstaged.stages
    body = jax.tree.leaves(st0.params["body"])
    assert body and st0.units[1] > 1 and st1.units[0] > 1   # both run periods
    assert all(a is b for a, b in zip(body,
                                      jax.tree.leaves(st1.params["body"])))
    assert sum(any(a is body[0] for a in out) for out in placed) == 1
    specs = jax.tree.leaves(T.model_specs(cfg, MeshPlan(("data", "model"),
                                                        (2, 2)))["body"],
                            is_leaf=lambda x: isinstance(x, P))
    assert [a.sharding for a in body] == [NamedSharding(mesh, s)
                                         for s in specs]
    tok = jax.ShapeDtypeStruct((B,), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    for st in sstaged.stages:
        window = jax.eval_shape(st.init_caches, tok)
        xin = tok if st.first else jax.ShapeDtypeStruct(
            (B, 1, cfg.d_model), jnp.float32,
            sharding=NamedSharding(mesh, P("data")))
        st.decode.lower(st.params, window, xin, tok).compile()


def test_grouped_expert_form_compiles(one_chip):
    """The grouped expert form (``jax.lax.ragged_dot``), which the expert
    layer picks on the TPU for large batches, compiles for a v5e chip at
    DeepSeek-V2-Lite's widths with all 64 experts held, over 2048 tokens
    of 6 pairs each."""
    from repro.models import mlp

    T, K, n, d, ff = 2048, 6, 64, 2048, 1408
    assert mlp.expert_form(n, T, K, "tpu") == "grouped"
    p = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
         for k, s in (("w_gate", (n, d, ff)), ("w_up", (n, d, ff)),
                      ("w_down", (n, ff, d)))}
    args = (jax.ShapeDtypeStruct((T, d), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((T * K,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((T * K,), jnp.float32, sharding=one_chip))
    jax.jit(mlp._grouped_form).lower(p, *args).compile()
