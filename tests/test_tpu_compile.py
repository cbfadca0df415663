"""Compile the four Pallas kernels, and the paged cache's scatter programs,
for a described TPU v5e (no chip needed).

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
from repro.kernels.flash_decode.kernel import flash_decode_pallas
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


@pytest.mark.parametrize("kind", ["decode", "prefill", "chunk"])
def test_paged_scatter_updates_slabs_in_place(one_chip, kind):
    """At the qwen3-1.7b benchmark cell's sizes (one stage of 14 layers,
    2 x 4 slots, cache 2448 in pages of 16), each scatter aliases the
    donated slabs to its output and copies no whole slab."""
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
