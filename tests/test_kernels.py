"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import (attention_dense_ref,
                                               flash_attention_ref)
from repro.kernels.flash_decode import kernel as flash_decode_kernel
from repro.kernels.flash_decode.kernel import (flash_decode_paged_pallas,
                                               flash_decode_pallas)
from repro.kernels.flash_decode.ref import (
    NEG_INF, combine_partials, decode_attention_ref, flash_decode_paged_ref,
    flash_decode_partial_ref)
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


def _repeat_partial_ref(q, k, v, *, k_offset=0, cur_pos=None,
                        sliding_window=0, k_positions=None):
    """Decode partials with K/V repeated to the query heads and V cast to
    float32 before P.V: the expanded form the grouped oracle must equal."""
    B, H, D = q.shape
    L, G = k.shape[1], H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q, k).astype(jnp.float32) * D ** -0.5
    if k_positions is not None:
        kpos, mask = k_positions, k_positions >= 0
    else:
        kpos = jnp.broadcast_to(k_offset + jnp.arange(L), (B, L))
        mask = jnp.ones((B, L), bool)
    if cur_pos is not None:
        mask &= kpos <= cur_pos[:, None]
        if sliding_window:
            mask &= kpos > cur_pos[:, None] - sliding_window
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(jnp.isfinite(m)[..., None], p, 0.0)
    return m, p.sum(axis=-1), jnp.einsum("bhk,bkhd->bhd", p,
                                         v.astype(jnp.float32))


def _grouped_inputs(G, KV=2, B=3, L=40, D=16):
    """Three slots of G query heads per KV head; ``_mask_kwargs`` gives
    each slot its own decode position."""
    q = jnp.asarray(RNG.normal(size=(B, G * KV, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, L, KV, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, L, KV, D)), jnp.float32)
    return q, k, v


def _mask_kwargs(mode, B, L):
    cur = jnp.asarray([5, 21, L - 1], jnp.int32)
    if mode == "full":
        return {}
    if mode == "cur_pos":
        return dict(cur_pos=cur)
    if mode == "sliding_window":
        return dict(cur_pos=cur, sliding_window=9)
    if mode == "k_positions":                     # ring buffer, -1 = empty
        kpos = RNG.integers(0, 3 * L, size=(B, L))
        kpos[RNG.random(size=(B, L)) < 0.3] = -1
        return dict(cur_pos=jnp.asarray([2 * L, 3 * L, L], jnp.int32),
                    sliding_window=L, k_positions=jnp.asarray(kpos, jnp.int32))
    if mode == "k_offset":
        return dict(cur_pos=cur + 30, k_offset=30)
    if mode == "masked_shard":                    # every key after cur_pos
        return dict(cur_pos=cur, k_offset=L)
    raise ValueError(mode)


@pytest.mark.parametrize("mode", ["full", "cur_pos", "sliding_window",
                                  "k_positions", "k_offset", "masked_shard"])
@pytest.mark.parametrize("G", [1, 2, 4, 8])
def test_grouped_decode_partials_equal_repeat_form(G, mode):
    """The grouped-query oracle (query head h reads KV head h // G, no
    expanded window, V promoted inside the dot) gives the repeat-and-float32
    form's (m, l, acc) and combined output to float32 rounding, in every
    mask mode."""
    q, k, v = _grouped_inputs(G)
    kw = _mask_kwargs(mode, q.shape[0], k.shape[1])
    got = flash_decode_partial_ref(q, k, v, **kw)
    want = _repeat_partial_ref(q, k, v, **kw)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == jnp.float32
        assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5)
    assert_allclose(np.asarray(combine_partials(*(a[None] for a in got))),
                    np.asarray(combine_partials(*(a[None] for a in want))),
                    rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("G", [1, 2, 4, 8])
def test_grouped_decode_sequence_shards_combine(G):
    """Grouped partials of two sequence shards (``S(seq)``; the second wholly
    past row 0's position) combine to the repeat form's whole-cache output."""
    q, k, v = _grouped_inputs(G)
    cur = jnp.asarray([5, 21, 39], jnp.int32)
    parts = [flash_decode_partial_ref(q, k[:, i:i + 20], v[:, i:i + 20],
                                      k_offset=i, cur_pos=cur)
             for i in (0, 20)]
    got = combine_partials(*(jnp.stack(a) for a in zip(*parts)))
    want = combine_partials(*(a[None] for a in _repeat_partial_ref(
        q, k, v, cur_pos=cur)))
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def _paged_table(page_len, pages_per_req, num_pages, rng):
    """Five slots' table rows and lengths over a pool of ``num_pages``:
    slot 0 parked (length 0, no row), slot 1 ends on a page boundary,
    slot 2 shares slot 1's first two pages and ends one past a boundary,
    slot 3 fills the window but its last position (``cache_len - 1``),
    slot 4 has an unmapped page inside its length. Entries past a length
    are ``-1``, or stale pages that must not be read."""
    L = page_len * pages_per_req
    rows = np.full((5, pages_per_req), -1, np.int32)
    free = list(rng.permutation(num_pages))
    lengths = np.array([0, 2 * page_len, 2 * page_len + 1, L - 1,
                        L - page_len // 2], np.int32)
    for b in range(1, 5):
        n = -(-int(lengths[b]) // page_len)
        rows[b, :n] = [free.pop() for _ in range(n)]
    rows[2, :2] = rows[1, :2]                          # shared prefix pages
    rows[1, 2] = free.pop()                            # stale, past length
    rows[4, 1] = -1                                    # unmapped inside
    return rows, lengths


#: bfloat16 slabs: the paged kernel rounds the softmax weights to V's dtype
#: before P.V, where ``flash_decode_partial_ref`` keeps them in float32.
#: Largest output gap over these cases (three table seeds): 0.0018; with
#: the weights rounded to float8 e4m3 instead, 0.031; kept in float32,
#: 2.4e-7 (interpret mode on the CPU).
PAGED_BF16_OUT_ATOL = 6e-3


def _attn_out(part):
    m, l, acc = part
    return np.asarray(acc) / np.maximum(np.asarray(l), 1e-30)[..., None]


@pytest.mark.parametrize("pages_per_block", [2, None])
@pytest.mark.parametrize("page_len", [16, 6])
@pytest.mark.parametrize("G", [1, 2, 8])
def test_paged_decode_partials_equal_gathered_window(G, page_len,
                                                     pages_per_block,
                                                     monkeypatch):
    """The paged flash-decode kernel, reading (P, num_pages, page_len, KV,
    D) slabs in place through table rows, and its jnp reference give the
    partials of ``flash_decode_partial_ref`` over the window gathered
    through the same rows: positions < length on mapped pages, none for a
    parked slot (where the kernel returns ``l = acc = 0``). Blocks of two
    pages (several blocks a slot, the last one partial) and of every page
    of a row."""
    _check_paged_decode(G, page_len, pages_per_block, jnp.float32,
                        monkeypatch)


@pytest.mark.parametrize("pages_per_block", [2, None])
@pytest.mark.parametrize("page_len", [16, 6])
@pytest.mark.parametrize("G", [1, 2, 8])
def test_paged_decode_bf16_slabs_within_weight_rounding(G, page_len,
                                                        pages_per_block,
                                                        monkeypatch):
    """The same cases over bfloat16 slabs, against the reference at the
    slabs' dtype: the kernel's ``acc`` carries its bfloat16 softmax
    weights, so its output ``acc / l`` is held to
    :data:`PAGED_BF16_OUT_ATOL`; ``m`` and ``l`` stay exact to float32, and
    the jnp reference to every partial."""
    _check_paged_decode(G, page_len, pages_per_block, jnp.bfloat16,
                        monkeypatch)


def _check_paged_decode(G, page_len, pages_per_block, dtype, monkeypatch):
    KV, D, P, R = 2, 16, 3, 5
    H, N, layer = KV * G, 40, 1
    if pages_per_block:
        monkeypatch.setattr(flash_decode_kernel, "BLOCK_ROWS",
                            pages_per_block * page_len * KV)
    k = jnp.asarray(RNG.normal(size=(P, N, page_len, KV, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(P, N, page_len, KV, D)), dtype)
    q = jnp.asarray(RNG.normal(size=(5, H, D)), dtype)
    rows, lengths = _paged_table(page_len, R, N, RNG)

    t = np.arange(page_len * R)
    page = rows[:, t // page_len]
    flat = np.maximum(page, 0) * page_len + t % page_len
    win_k = k[layer].reshape(N * page_len, KV, D)[flat]
    win_v = v[layer].reshape(N * page_len, KV, D)[flat]
    kpos = np.where((page >= 0) & (t < lengths[:, None]), t, -1)
    want = flash_decode_partial_ref(q, win_k, win_v,
                                    k_positions=jnp.asarray(kpos))
    live = lengths > 0
    own = flash_decode_partial_ref(q, win_k[:, :1], win_v[:, :1])
    for fn, exact in ((flash_decode_paged_ref, True),
                      (functools.partial(flash_decode_paged_pallas,
                                         interpret=True),
                       dtype == jnp.float32)):
        atol = 1e-5 if exact else PAGED_BF16_OUT_ATOL
        got = fn(q, k, v, jnp.asarray(rows), jnp.asarray(lengths), layer)
        assert all(g.dtype == jnp.float32 for g in got)
        for g, w in zip(got[:3 if exact else 2], want):
            assert_allclose(np.asarray(g)[live], np.asarray(w)[live],
                            rtol=1e-5, atol=1e-5)
        assert_allclose(_attn_out(got)[live], _attn_out(want)[live],
                        rtol=1e-5, atol=atol)
        assert not np.asarray(got[1])[~live].any()
        assert not np.asarray(got[2])[~live].any()
        # joined with a further partial, as the decode joins the new
        # token's own, every slot gives the same output (the parked one too)
        joined = [combine_partials(*(jnp.stack(pair)
                                     for pair in zip(part, own)))
                  for part in (got, want)]
        assert_allclose(np.asarray(joined[0]), np.asarray(joined[1]),
                        rtol=1e-5, atol=atol)


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
