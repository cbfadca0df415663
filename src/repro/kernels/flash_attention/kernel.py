"""Pallas TPU kernel: blocked causal/sliding-window GQA flash attention.

Grid: (batch, q_heads, q_blocks, kv_blocks) with kv innermost; the online-
softmax state (m, l, acc) lives in VMEM scratch across kv tiles and the
output tile is emitted on the last kv tile. Block shapes default to
(128 q x 128 kv) — MXU-aligned (head_dim is the lane dim, multiples of 128
for all assigned archs except whisper's 64, still VPU-tileable).

GQA is expressed in the kv index_map: q head h reads kv head h * KV // H —
no materialized head broadcast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, n_kblocks: int, seq_q: int,
                  seq_k: int, causal: bool, sliding_window: int,
                  q_offset: int, sm_scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)             # (block_q, d)
    k = k_ref[0, 0].astype(jnp.float32)             # (block_k, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    qpos = (q_offset + qi * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
    kpos = (ki * block_k
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    mask = kpos < seq_k                             # kv padding
    row_valid = (qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)) < seq_q  # q padding
    mask &= row_valid
    if causal:
        mask &= qpos >= kpos
    if sliding_window:
        mask &= kpos > qpos - sliding_window
    s = jnp.where(mask, s, NEG_INF)

    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    scale = jnp.exp(m_old - m_new)
    l_scr[...] = l_scr[...] * scale + p.sum(axis=1)
    acc_scr[...] = (acc_scr[...] * scale[:, None]
                    + jax.lax.dot_general(
                        p, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(ki == n_kblocks - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           sliding_window: int = 0, q_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           sm_scale=None, interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D), H % KV == 0.

    Matches :func:`repro.kernels.flash_attention.ref.flash_attention_ref`.
    """
    B, Sq, H, D = q.shape
    _, Sk, KV, Dv = k.shape[0], k.shape[1], k.shape[2], v.shape[3]
    assert H % KV == 0
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    block_q = min(block_q, max(8, Sq))
    block_k = min(block_k, max(8, Sk))
    pq, pk = (-Sq) % block_q, (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    # layout: (B, H, S, D) so the S x D tile is contiguous per (b, h)
    qp = qp.transpose(0, 2, 1, 3)
    kp = kp.transpose(0, 2, 1, 3)
    vp = vp.transpose(0, 2, 1, 3)
    nq, nk = qp.shape[2] // block_q, kp.shape[2] // block_k

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, n_kblocks=nk,
        seq_q=Sq, seq_k=Sk, causal=causal, sliding_window=sliding_window,
        q_offset=q_offset, sm_scale=sm_scale)

    group = H // KV
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out.transpose(0, 2, 1, 3)[:, :Sq]
