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
