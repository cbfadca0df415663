"""Pallas TPU kernel: Mamba-2 SSD intra-chunk compute.

Grid: (batch, heads, chunks), sequential over chunks: the inter-chunk state
recurrence is carried in VMEM scratch (h: (P, N)), so one kernel launch
covers the whole sequence — intra-chunk work is dense MXU matmuls
(Q x Q decay-masked scores, Q x N state outer products), the recurrence is a
cheap elementwise update once per chunk.

This is the TPU adaptation of the SSD algorithm: the GPU version leans on
warp-level scans; on TPU the chunk-quadratic form feeds the MXU and the
cross-chunk dependency becomes a scalar-decay multiply in VMEM.

Layout: every block is 2-D in its last two dims. ``dt`` arrives twice, as a
``(1, Q)`` row and a ``(Q, 1)`` column per chunk (free reshapes of one
array), so the within-chunk cumulative log-decay is built as masked row and
column sums — no in-kernel transpose or scan. ``x`` also arrives transposed
``(P, Q)`` for the chunk-end state matmul. The per-head scalars ``A`` and
``D`` are whole-array SMEM operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

#: fp32 operands (decay-weighted scores, the carried state) take the MXU's
#: full-precision passes: the default single bf16 pass loses ~2^-9 of each
#: term, and y sums terms that largely cancel
_HI = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, xt_ref, dtr_ref, dtc_ref, a_ref, b_ref, c_ref, d_ref,
                y_ref, hout_ref, h_scr, *, Q: int, n_chunks: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    xt = xt_ref[0, 0].astype(jnp.float32)        # (P, Q)
    dt_row = dtr_ref[0, 0, 0]                    # (1, Q)
    dt_col = dtc_ref[0, 0, 0]                    # (Q, 1)
    A = a_ref[hi]                                # scalar (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)         # (Q, N)
    D = d_ref[hi]

    # inclusive cumulative log-decay cs_i = sum_{j <= i} dt_j * A, as a
    # column (Q, 1) and as a row (1, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = ii >= jj
    cs_col = jnp.sum(jnp.where(lower, dt_row * A, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(ii <= jj, dt_col * A, 0.0), axis=0,
                     keepdims=True)
    total = jnp.sum(dt_row * A, axis=1, keepdims=True)          # (1, 1)

    # intra-chunk decay matrix L[i, j] = exp(cs_i - cs_j) for i >= j
    Lmat = jnp.where(lower, jnp.exp(cs_col - cs_row), 0.0)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 precision=_HI,
                                 preferred_element_type=jnp.float32)  # (Q, Q)
    w = scores * Lmat * dt_row
    y_diag = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                 precision=_HI,
                                 preferred_element_type=jnp.float32)  # (Q, P)

    # contribution of the carried state: y_off[i] = exp(cs_i) * C_i . h
    h = h_scr[...]                               # (P, N)
    ch = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                             precision=_HI,
                             preferred_element_type=jnp.float32)      # (Q, P)
    y_off = jnp.exp(cs_col) * ch

    y_ref[0, 0] = (y_diag + y_off + x * D).astype(y_ref.dtype)

    # chunk-end state: h' = exp(sum da) * h + sum_j exp(cs_Q - cs_j) dt_j x_j B_j
    dec = jnp.exp(total - cs_row) * dt_row       # (1, Q)
    S = jax.lax.dot_general(xt * dec, Bm, (((1,), (0,)), ((), ())),
                            precision=_HI,
                            preferred_element_type=jnp.float32)       # (P, N)
    h_scr[...] = jnp.exp(total) * h + S

    @pl.when(ci == n_chunks - 1)
    def _emit():
        hout_ref[0, 0] = h_scr[...]


def ssd_scan_pallas(x, dt, A, Bm, Cm, D, *, chunk: int = 128,
                    interpret: bool = False):
    """x: (B, L, H, P); dt: (B, L, H); A, D: (H,); Bm, Cm: (B, L, G, N).

    Returns (y, hT) matching
    :func:`repro.kernels.ssd_scan.ref.ssd_chunked_ref` (G groups expanded in
    the index map, no materialized repeat).
    """
    B, L, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        def zf(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, Bm, Cm = zf(x), zf(dt), zf(Bm), zf(Cm)
    Lp = x.shape[1]
    nc = Lp // Q
    hg = H // G

    # layout: head-major so per-(b,h) tiles are contiguous
    xt = x.transpose(0, 2, 1, 3)                  # (B, H, Lp, P)
    xtt = x.transpose(0, 2, 3, 1)                 # (B, H, P, Lp)
    dtt = dt.astype(jnp.float32).transpose(0, 2, 1)          # (B, H, Lp)
    dt_row = dtt.reshape(B, H, nc, 1, Q)
    dt_col = dtt.reshape(B, H, nc, Q, 1)
    bt = Bm.transpose(0, 2, 1, 3)                 # (B, G, Lp, N)
    ct = Cm.transpose(0, 2, 1, 3)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    kernel = functools.partial(_ssd_kernel, Q=Q, n_chunks=nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda b, h, c: (b, h, c, 0, 0)),
            smem,
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h // hg, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h // hg, c, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lp, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, xtt, dt_row, dt_col, A.astype(jnp.float32), bt, ct,
      D.astype(jnp.float32))
    return y.transpose(0, 2, 1, 3)[:, :L], hT
