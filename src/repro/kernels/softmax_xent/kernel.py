"""Pallas TPU kernel: fused sharded-vocab softmax cross-entropy local stats.

The paper's Fig 11b pattern: each vocab shard reduces LOCALLY (max, sum-exp,
label-logit gather) in one pass over VMEM tiles; the tiny (m, s, z) stats are
combined across shards by the SBP partial-value boxing outside.

Grid: (row_blocks, vocab_blocks) — vocab is the innermost (fastest) axis so
the running stats live in VMEM scratch across vocab tiles and are emitted on
the last tile. Tiles are MXU/VPU aligned: (block_rows x block_vocab) with
block_vocab a multiple of 128. Per-row vectors (labels, stats) are
``(rows, 1)`` columns so every block is 2-D; the shard's vocab offset is a
scalar-prefetch operand in SMEM. The label gather is an ``iota == label``
masked row sum (exactly one column matches), which the TPU lowers to plain
VPU compares and a lane reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _xent_kernel(voff_ref, logits_ref, labels_ref,
                 m_ref, s_ref, z_ref,
                 m_scr, s_scr, z_scr,
                 *, block_v: int, n_vblocks: int, vocab_local: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)
        z_scr[...] = jnp.zeros_like(z_scr)

    x = logits_ref[...].astype(jnp.float32)          # (bR, bV)
    labels = labels_ref[...]                         # (bR, 1)
    voff = voff_ref[0]                               # global col of shard

    # mask the padding tail of the last vocab tile
    tile_col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = vi * block_v + tile_col < vocab_local
    x = jnp.where(valid, x, NEG_INF)

    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, x.max(axis=1, keepdims=True))
    scale = jnp.exp(m_old - m_new)
    s_scr[...] = s_scr[...] * scale + jnp.exp(x - m_new).sum(
        axis=1, keepdims=True)
    m_scr[...] = m_new

    # label gather: the label's local column may fall in this tile
    shard_col = labels - voff
    local_col = shard_col - vi * block_v             # (bR, 1)
    hit = (tile_col == local_col) & valid & (shard_col >= 0)
    z_scr[...] = z_scr[...] + jnp.where(hit, x, 0.0).sum(axis=1,
                                                         keepdims=True)

    @pl.when(vi == n_vblocks - 1)
    def _emit():
        m_ref[...] = m_scr[...]
        s_ref[...] = s_scr[...]
        z_ref[...] = z_scr[...]


def xent_local_stats_pallas(logits, labels, vocab_offset, *,
                            block_rows: int = 256, block_v: int = 512,
                            interpret: bool = False):
    """logits: (N, Vl); labels: (N,) global ids; vocab_offset: scalar.

    Returns (m, s, z) local stats, identical to
    :func:`repro.kernels.softmax_xent.ref.local_stats_ref`.
    """
    N, Vl = logits.shape
    block_rows = min(block_rows, N)
    block_v = min(block_v, max(128, Vl))
    pr = (-N) % block_rows
    pv = (-Vl) % block_v
    lp = jnp.pad(logits, ((0, pr), (0, pv)))
    lbl = jnp.pad(labels.astype(jnp.int32), (0, pr))[:, None]
    Np, Vp = lp.shape
    n_r, n_v = Np // block_rows, Vp // block_v
    voff = jnp.reshape(jnp.asarray(vocab_offset, jnp.int32), (1,))

    kernel = functools.partial(_xent_kernel, block_v=block_v, n_vblocks=n_v,
                               vocab_local=Vl)
    col = pl.BlockSpec((block_rows, 1), lambda r, v, off: (r, 0))
    stat = jax.ShapeDtypeStruct((Np, 1), jnp.float32)
    m, s, z = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_r, n_v),
            in_specs=[
                pl.BlockSpec((block_rows, block_v), lambda r, v, off: (r, v)),
                col,
            ],
            out_specs=[col, col, col],
            scratch_shapes=[pltpu.VMEM((block_rows, 1), jnp.float32)] * 3,
        ),
        out_shape=[stat, stat, stat],
        interpret=interpret,
    )(voff, lp, lbl)
    return m[:N, 0], s[:N, 0], z[:N, 0]
