"""Pure-jnp oracle for single-token flash-decode with partial-value output.

The distributed decode path shards the KV cache along the sequence axis over
the ``model`` mesh axis (SBP ``S(seq)``). Each shard produces *partial*
attention statistics — exactly the paper's partial-value signature, with a
non-sum reduction:

    m_shard   : P(max)   running max of scores
    acc_shard : P(sum)   exp-weighted value accumulation (after rescale)
    l_shard   : P(sum)   exp sum

:func:`flash_decode_partial_ref` computes one shard's contribution;
:func:`combine_partials` is the logical reduction (what the boxing op
``P -> B`` performs, here as pmax/psum pairs).

Grouped-query attention is contracted against the cache as stored: the
``H`` query heads are viewed as ``(KV, G)`` groups, ``G = H // KV``, so query
head ``h`` reads KV head ``h // G`` (the order ``jnp.repeat`` would give) and
no ``G``-fold copy of the K/V window is ever made.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_decode_partial_ref(q, k, v, *, k_offset: int = 0,
                             cur_pos=None, sliding_window: int = 0,
                             k_positions=None,
                             sm_scale: Optional[float] = None):
    """Partial attention of a 1-token query over one KV-cache shard.

    q: (B, H, D); k, v: (B, L, KV, D) — this shard's cache slice;
    ``k_offset``: absolute position of k[0]; ``cur_pos``: (B,) current decode
    position (entries at or beyond it are masked: cache may be pre-allocated).
    ``k_positions``: (B, L) explicit absolute position per slot (ring-buffer
    sliding-window caches; -1 = empty slot), overrides ``k_offset``.
    Returns (m, l, acc): (B,H), (B,H), (B,H,D) partials.

    Query head ``h`` attends over KV head ``h // G`` (``G = H // KV``): q is
    viewed as ``(B, KV, G, D)`` and both products contract against the
    unexpanded ``(B, L, KV, D)`` cache. Scores are q x k at the inputs'
    dtype, cast to float32 before scaling; the accumulator is the float32
    softmax weights against V, which the dot promotes to float32 itself.
    """
    B, H, D = q.shape
    _, L, KV, _ = k.shape
    G = H // KV
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, KV, G, D)
    s = jnp.einsum("bngd,bknd->bngk", qg, k).astype(jnp.float32) * sm_scale
    s = s.reshape(B, H, L)
    if k_positions is not None:
        kpos = k_positions                                   # (B, L)
        mask = kpos >= 0
    else:
        kpos = jnp.broadcast_to(k_offset + jnp.arange(L), (B, L))
        mask = jnp.ones((B, L), bool)
    if cur_pos is not None:
        mask &= kpos <= cur_pos[:, None]
        if sliding_window:
            mask &= kpos > cur_pos[:, None] - sliding_window
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m = s.max(axis=-1)                             # (B, H)  P(max)
    p = jnp.exp(s - m[..., None])
    # fully-masked shards: m = -inf -> p = exp(-inf - -inf); force 0
    p = jnp.where(jnp.isfinite(m)[..., None], p, 0.0)
    l = p.sum(axis=-1)                             # (B, H)  P(sum) after rescale
    acc = jnp.einsum("bngk,bknd->bngd", p.reshape(B, KV, G, L), v)
    return m, l, acc.reshape(B, H, v.shape[-1])


def combine_partials(m, l, acc, axis_name: Optional[str] = None):
    """Reduce shard partials to the attention output.

    With ``axis_name``: the distributed combine (pmax + psum inside
    shard_map). Without: combines a stacked leading shard axis (oracle mode).
    """
    if axis_name is not None:
        m_g = jax.lax.pmax(m, axis_name)
        scale = jnp.where(jnp.isfinite(m), jnp.exp(m - m_g), 0.0)
        l_g = jax.lax.psum(l * scale, axis_name)
        acc_g = jax.lax.psum(acc * scale[..., None], axis_name)
    else:
        m_g = m.max(axis=0)
        scale = jnp.where(jnp.isfinite(m), jnp.exp(m - m_g[None]), 0.0)
        l_g = (l * scale).sum(axis=0)
        acc_g = (acc * scale[..., None]).sum(axis=0)
    return (acc_g / jnp.maximum(l_g, 1e-30)[..., None])


def decode_attention_ref(q, k, v, cur_pos, *, sliding_window: int = 0,
                         sm_scale=None):
    """Single-shard (logical) decode attention oracle."""
    m, l, acc = flash_decode_partial_ref(
        q, k, v, k_offset=0, cur_pos=cur_pos, sliding_window=sliding_window,
        sm_scale=sm_scale)
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def flash_decode_paged_ref(q, k_slab, v_slab, rows, lengths, layer, *,
                           sm_scale: Optional[float] = None):
    """The paged kernel's math in jnp: gather slot ``b``'s window of layer
    ``layer`` through its table row ``rows[b]`` and take its partial over
    the positions ``< lengths[b]`` on mapped pages (``l = acc = 0`` where
    there are none).

    q: (B, H, D); k_slab, v_slab: (P, num_pages, page_len, KV, D);
    rows: (B, pages_per_req) int32 (-1 = unmapped); lengths: (B,) int32.
    """
    page_len = k_slab.shape[2]
    L = rows.shape[1] * page_len
    pos = jnp.arange(L)
    page = rows[:, pos // page_len]                          # (B, L)
    ok = (page >= 0) & (pos[None, :] < lengths[:, None])
    flat = jnp.maximum(page, 0) * page_len + pos[None, :] % page_len

    def window(slab):
        s = slab[layer]
        return jnp.take(s.reshape((-1,) + s.shape[2:]), flat, axis=0)

    m, l, acc = flash_decode_partial_ref(
        q, window(k_slab), window(v_slab),
        k_positions=jnp.where(ok, pos[None, :], -1), sm_scale=sm_scale)
    none = ~ok.any(axis=1)[:, None]
    return (m, jnp.where(none, 0.0, l),
            jnp.where(none[..., None], 0.0, acc))
