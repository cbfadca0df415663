"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import (attention_dense_ref,
                                               flash_attention_ref)
from repro.kernels.flash_decode.kernel import flash_decode_pallas
from repro.kernels.flash_decode.ref import (
    combine_partials, decode_attention_ref)
from repro.kernels.softmax_xent.kernel import xent_local_stats_pallas
from repro.kernels.softmax_xent.ref import (combine_stats, local_stats_ref,
                                            softmax_xent_ref)
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_chunked_ref, ssd_sequential_ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Sk, H, KV, D, Dv, causal, window, dtype
    (2, 50, 50, 4, 2, 16, 16, True, 0, jnp.float32),
    (1, 33, 33, 4, 4, 32, 16, True, 7, jnp.float32),     # MLA-ish Dv != D
    (2, 16, 64, 2, 1, 16, 16, False, 0, jnp.float32),    # cross attention
    (1, 128, 128, 8, 2, 64, 64, True, 0, jnp.bfloat16),
    (1, 17, 65, 2, 2, 8, 8, True, 0, jnp.float32),       # ragged + offset
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_kernel_vs_oracle(case):
    B, Sq, Sk, H, KV, D, Dv, causal, w, dt = case
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, D)), dt)
    k = jnp.asarray(RNG.normal(size=(B, Sk, KV, D)), dt)
    v = jnp.asarray(RNG.normal(size=(B, Sk, KV, Dv)), dt)
    qoff = Sk - Sq if causal else 0
    got = flash_attention_pallas(q, k, v, causal=causal, sliding_window=w,
                                 q_offset=qoff, block_q=16, block_k=16,
                                 interpret=True)
    want = attention_dense_ref(q, k, v, causal=causal, sliding_window=w,
                               q_offset=qoff)
    assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                    **_tol(dt))


@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 64)])
def test_flash_ref_block_invariance(blocks):
    """The jnp flash ref must be block-size invariant."""
    bq, bk = blocks
    q = jnp.asarray(RNG.normal(size=(2, 40, 4, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 40, 2, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 40, 2, 16)), jnp.float32)
    got = flash_attention_ref(q, k, v, causal=True, block_q=bq, block_k=bk)
    want = attention_dense_ref(q, k, v, causal=True)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

DECODE_CASES = [
    (2, 4, 2, 16, 64, 0, jnp.float32),
    (1, 8, 8, 32, 100, 17, jnp.float32),
    (3, 4, 1, 64, 96, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_flash_decode_kernel_vs_oracle(case):
    B, H, KV, D, L, w, dt = case
    q = jnp.asarray(RNG.normal(size=(B, H, D)), dt)
    k = jnp.asarray(RNG.normal(size=(B, L, KV, D)), dt)
    v = jnp.asarray(RNG.normal(size=(B, L, KV, D)), dt)
    cur = jnp.asarray(RNG.integers(10, L, size=(B,)), jnp.int32)
    m1, l1, a1 = flash_decode_pallas(q, k, v, cur_pos=cur, sliding_window=w,
                                     block_k=16, interpret=True)
    o1 = a1 / jnp.maximum(l1, 1e-30)[..., None]
    want = decode_attention_ref(q, k, v, cur, sliding_window=w)
    assert_allclose(np.asarray(o1, np.float32), np.asarray(want, np.float32),
                    **_tol(dt))


def test_flash_decode_shard_combine():
    """Kernel partials from disjoint shards combine to the full attention —
    the P(max)/P(sum) algebra the distributed decode uses."""
    B, H, KV, D, L = 2, 4, 2, 16, 64
    q = jnp.asarray(RNG.normal(size=(B, H, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, L, KV, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, L, KV, D)), jnp.float32)
    cur = jnp.asarray([40, 63], jnp.int32)
    parts = [flash_decode_pallas(q, k[:, i*16:(i+1)*16], v[:, i*16:(i+1)*16],
                                 cur_pos=cur, k_offset=i*16, block_k=8,
                                 interpret=True)
             for i in range(4)]
    m = jnp.stack([p[0] for p in parts])
    l = jnp.stack([p[1] for p in parts])
    a = jnp.stack([p[2] for p in parts])
    got = combine_partials(m, l, a)
    want = decode_attention_ref(q, k, v, cur)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# softmax xent
# ---------------------------------------------------------------------------

XENT_CASES = [
    (64, 1000, 0, jnp.float32),
    (100, 700, 2100, jnp.float32),
    (7, 130, 130, jnp.float32),
    (256, 2048, 4096, jnp.bfloat16),
]


@pytest.mark.parametrize("case", XENT_CASES)
def test_xent_kernel_vs_oracle(case):
    N, Vl, off, dt = case
    logits = jnp.asarray(RNG.normal(size=(N, Vl)) * 3, dt)
    labels = jnp.asarray(RNG.integers(0, 3 * Vl, size=(N,)), jnp.int32)
    m1, s1, z1 = xent_local_stats_pallas(logits, labels, off, block_v=256,
                                         interpret=True)
    m2, s2, z2 = local_stats_ref(logits, labels, off)
    tol = _tol(dt)
    assert_allclose(np.asarray(m1), np.asarray(m2), **tol)
    assert_allclose(np.asarray(s1), np.asarray(s2), **tol)
    assert_allclose(np.asarray(z1), np.asarray(z2), **tol)


def test_xent_shard_combine_matches_full():
    """Four vocab shards' kernel stats combine to the dense softmax-xent."""
    N, V = 32, 1024
    logits = jnp.asarray(RNG.normal(size=(N, V)) * 2, jnp.float32)
    labels = jnp.asarray(RNG.integers(0, V, size=(N,)), jnp.int32)
    Vl = V // 4
    stats = [xent_local_stats_pallas(logits[:, i*Vl:(i+1)*Vl], labels, i*Vl,
                                     block_v=128, interpret=True)
             for i in range(4)]
    m = jnp.stack([s[0] for s in stats])
    s_ = jnp.stack([s[1] for s in stats])
    z = jnp.stack([s[2] for s in stats])
    got = combine_stats(m, s_, z)
    want = softmax_xent_ref(logits, labels)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    (2, 67, 4, 8, 16, 1, 16, jnp.float32),
    (1, 128, 2, 16, 8, 2, 32, jnp.float32),
    (1, 64, 4, 32, 16, 1, 128, jnp.float32),   # chunk > L
    (2, 96, 4, 16, 16, 1, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_kernel_vs_sequential_oracle(case):
    B, L, H, P, N, G, Q, dt = case
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), dt)
    dtv = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, L, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, L, G, N)), dt)
    Cm = jnp.asarray(RNG.normal(size=(B, L, G, N)), dt)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    y1, h1 = ssd_scan_pallas(x, dtv, A, Bm, Cm, D, chunk=Q, interpret=True)
    y2, h2 = ssd_sequential_ref(x, dtv, A, Bm, Cm, D)
    tol = _tol(dt)
    assert_allclose(np.asarray(y1, np.float32), np.asarray(y2, np.float32),
                    **tol)
    assert_allclose(np.asarray(h1), np.asarray(h2),
                    rtol=max(tol["rtol"], 1e-4), atol=max(tol["atol"], 1e-4))


def test_ssd_chunked_ref_matches_sequential():
    B, L, H, P, N, G = 2, 77, 4, 8, 16, 1
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), jnp.float32)
    dtv = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, L, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    y1, h1 = ssd_chunked_ref(x, dtv, A, Bm, Cm, D, chunk=16)
    y2, h2 = ssd_sequential_ref(x, dtv, A, Bm, Cm, D)
    assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)
    assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4, atol=1e-4)
