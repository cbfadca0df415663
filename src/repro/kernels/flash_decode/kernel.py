"""Pallas TPU kernel: split-KV flash-decode partials for one-token decode.

Grid: (batch, kv_splits). Each split attends the query (all heads at once —
the (H, D) tile is MXU-friendly for H >= 8) over its KV-cache slice and
emits partial (m, l, acc). The partials are P(max)/P(sum) values combined by
the SBP boxing (pmax/psum) across devices and by
:func:`repro.kernels.flash_decode.ref.combine_partials` across splits.

The cache's ``(L, KV, D)`` block is read as the free row-major view
``(L * KV, D)``: row ``r`` holds position ``r // KV`` of kv head ``r % KV``.
One ``(H, D) x (D, L * KV)`` matmul scores every q head against every row and
a GQA mask keeps the rows of each q head's own kv head, so the kernel needs
no in-kernel transpose or head repeat. The decode positions ``cur_pos`` are
a scalar-prefetch operand in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
                   block_k: int, seq_k: int, k_offset: int, kv_heads: int,
                   sliding_window: int, sm_scale: float, group: int):
    b = pl.program_id(0)
    si = pl.program_id(1)

    q = q_ref[0].astype(jnp.float32)                  # (H, D)
    k = k_ref[0].astype(jnp.float32)                  # (block_k * KV, D)
    v = v_ref[0].astype(jnp.float32)                  # (block_k * KV, Dv)
    cur = pos_ref[b]

    H = q.shape[0]
    rows = k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    r = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
    h = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0)
    kpos = k_offset + si * block_k + r // kv_heads
    mask = ((r % kv_heads == h // group)
            & (kpos < k_offset + seq_k) & (kpos <= cur))
    if sliding_window:
        mask &= kpos > cur - sliding_window
    s = jnp.where(mask, s, NEG_INF)

    m = s.max(axis=1, keepdims=True)                  # (H, 1)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = p.sum(axis=1, keepdims=True)
    acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)

    m_ref[0, 0] = m
    l_ref[0, 0] = l
    acc_ref[0, 0] = acc


def flash_decode_pallas(q, k, v, *, cur_pos, k_offset: int = 0,
                        sliding_window: int = 0, block_k: int = 512,
                        sm_scale=None, interpret: bool = False):
    """q: (B, H, D); k, v: (B, L, KV, D/Dv); cur_pos: (B,).

    Returns per-split partials combined over splits: (m, l, acc) with shapes
    (B, H), (B, H), (B, H, Dv) — identical to
    :func:`repro.kernels.flash_decode.ref.flash_decode_partial_ref`.
    """
    B, H, D = q.shape
    _, L, KV, Dv = k.shape[0], k.shape[1], k.shape[2], v.shape[3]
    group = H // KV
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    block_k = min(block_k, max(8, L))
    pk = (-L) % block_k
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    ns = kp.shape[1] // block_k
    kp = kp.reshape(B, -1, D)                         # (B, Lp * KV, D)
    vp = vp.reshape(B, -1, Dv)

    kernel = functools.partial(
        _decode_kernel, block_k=block_k, seq_k=L, k_offset=k_offset,
        kv_heads=KV, sliding_window=sliding_window, sm_scale=sm_scale,
        group=group)

    rows = block_k * KV
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, ns),
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, s, pos: (b, 0, 0)),
                pl.BlockSpec((1, rows, D), lambda b, s, pos: (b, s, 0)),
                pl.BlockSpec((1, rows, Dv), lambda b, s, pos: (b, s, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, H, 1), lambda b, s, pos: (b, s, 0, 0)),
                pl.BlockSpec((1, 1, H, 1), lambda b, s, pos: (b, s, 0, 0)),
                pl.BlockSpec((1, 1, H, Dv), lambda b, s, pos: (b, s, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, ns, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, H, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(cur_pos.astype(jnp.int32), q, kp, vp)
    m, l = m[..., 0], l[..., 0]

    # combine the split partials (second-level P(max)/P(sum) reduction)
    m_g = m.max(axis=1)                                        # (B, H)
    scale = jnp.where(jnp.isfinite(m), jnp.exp(m - m_g[:, None]), 0.0)
    l_g = (l * scale).sum(axis=1)
    acc_g = (acc * scale[..., None]).sum(axis=1)
    return m_g, l_g, acc_g


# ---------------------------------------------------------------------------
# paged variant: the page slabs read in place through the table rows
# ---------------------------------------------------------------------------

#: most ``(position, kv head)`` rows one block of pages holds (2048 rows of
#: 128 bf16 lanes: 512 KiB a buffer, four buffers double-buffering K and V)
BLOCK_ROWS = 2048


def _paged_decode_kernel(len_ref, rows_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         m_ref, l_ref, acc_ref, k_buf, v_buf, sems, *,
                         page_len: int, pages_per_block: int,
                         pages_per_req: int, kv_heads: int, group: int,
                         sm_scale: float):
    b = pl.program_id(0)
    length = len_ref[b]
    layer = layer_ref[0]
    ppb = pages_per_block
    rows_pp = page_len * kv_heads                     # rows of one page
    block_len = page_len * ppb                        # positions of a block
    n_blocks = (length + block_len - 1) // block_len

    @pl.when(b == 0)
    def _():
        # rows of pages a block does not load keep what the buffer held:
        # zeros, or an earlier page's (finite) values, which a zero weight
        # then removes from P.V exactly
        v_buf[...] = jnp.zeros_like(v_buf)

    def page(blk, j):
        """Page ``j`` of block ``blk``: its id, and whether to read it (it
        holds a position < length and is mapped)."""
        pj = blk * ppb + j
        pid = rows_ref[b * pages_per_req
                       + jnp.minimum(pj, pages_per_req - 1)]
        return jnp.maximum(pid, 0), (pj * page_len < length) & (pid >= 0)

    def each_page(blk, slot, kv, act):
        """``act(copy)`` on the copy of each page of block ``blk`` to read,
        of K (``kv`` 0) and/or V (1) into buffer ``slot``; rolled loops keep
        the kernel's trace short."""
        def one(j, carry):
            pid, live = page(blk, j)

            @pl.when(live)
            def _():
                for i in kv:
                    hbm, buf = ((k_hbm, k_buf), (v_hbm, v_buf))[i]
                    act(pltpu.make_async_copy(
                        hbm.at[layer, pid],
                        buf.at[slot, pl.ds(j * rows_pp, rows_pp)],
                        sems.at[i, slot]))
            return carry
        jax.lax.fori_loop(0, ppb, one, 0)

    def start(blk, slot):
        each_page(blk, slot, (0, 1), lambda cp: cp.start())

    q = q_ref[0]                                      # (H, D)
    H = q.shape[0]
    rows = ppb * rows_pp
    r = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    h = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0)
    own_head = (jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
                % kv_heads) == h // group
    r_pos, r_page = r // kv_heads, r // rows_pp

    @pl.when(n_blocks > 0)
    def _():
        start(0, 0)

    def body(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            start(blk + 1, 1 - slot)

        mapped = jax.lax.fori_loop(               # bit j: page j is read
            0, ppb, lambda j, bits: bits | jnp.where(
                page(blk, j)[1], jnp.left_shift(1, j), 0), 0)
        ok = ((blk * block_len + r_pos < length)
              & (((mapped >> r_page) & 1) == 1))      # (1, rows)
        mask = own_head & ok
        each_page(blk, slot, (0,), lambda cp: cp.wait())
        k = k_buf[slot].astype(q.dtype)               # (rows, D)
        # scores at the query's dtype, then float32, as the reference does
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s.astype(q.dtype).astype(jnp.float32) * sm_scale
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        each_page(blk, slot, (1,), lambda cp: cp.wait())
        v = v_buf[slot].astype(q.dtype)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return (m_new, alpha * l + p.sum(axis=1, keepdims=True),
                alpha * acc + pv)

    Dv = acc_ref.shape[-1]
    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((H, 1), NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, Dv), jnp.float32)))
    m_ref[0] = m
    l_ref[0] = l
    acc_ref[0] = acc


def flash_decode_paged_pallas(q, k_slab, v_slab, rows, lengths, layer, *,
                              sm_scale=None, interpret: bool = False):
    """Split-KV flash-decode partials read from page slabs in place.

    q: (B, H, D); k_slab, v_slab: (P, num_pages, page_len, KV, D/Dv), the
    stage's stacked slabs as stored; rows: (B, pages_per_req) int32 page
    table rows (-1 = unmapped); lengths: (B,) int32; layer: scalar index
    into the slabs' leading axis.

    Slot ``b`` attends its positions ``< lengths[b]`` that lie on mapped
    pages; a slot of length 0 (parked) reads nothing. A block of pages is
    fetched per step, page by page, from the slab viewed free as
    ``(P, num_pages, page_len * KV, D)``; the next block's copies run while
    the current one is scored, and no copy is issued for a page past the
    length or unmapped. Returns (m, l, acc): (B, H), (B, H), (B, H, Dv)
    float32, the partials of :func:`repro.kernels.flash_decode.ref.
    flash_decode_partial_ref` over those positions, with ``l = acc = 0``
    where there are none. The softmax runs blockwise online in float32;
    scores are rounded to the query's dtype first, as the reference's
    program states (XLA on the TPU fuses that rounding away), and the
    weights ``p`` to V's dtype before ``P.V`` (the reference keeps them in
    float32), so with bfloat16 slabs the partials agree with the
    reference's to bfloat16 rounding of ``p``.
    """
    B, H, D = q.shape
    P, N, page_len, KV, _ = k_slab.shape
    Dv = v_slab.shape[-1]
    R = rows.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    rows_pp = page_len * KV
    ppb = max(1, min(R, BLOCK_ROWS // rows_pp, 30))   # a bit per page
    kf = k_slab.reshape(P, N, rows_pp, D)
    vf = v_slab.reshape(P, N, rows_pp, Dv)

    kernel = functools.partial(
        _paged_decode_kernel, page_len=page_len, pages_per_block=ppb,
        pages_per_req=R, kv_heads=KV, group=H // KV, sm_scale=sm_scale)
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, H, 1), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, H, 1), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, H, Dv), lambda b, *_: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, ppb * rows_pp, D), k_slab.dtype),
                pltpu.VMEM((2, ppb * rows_pp, Dv), v_slab.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Dv), jnp.float32),
        ],
        # grid steps share the buffers' zeroed start: run them in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), rows.astype(jnp.int32).reshape(-1),
      jnp.asarray(layer, jnp.int32).reshape(1), q, kf, vf)
    return m[..., 0], l[..., 0], acc
