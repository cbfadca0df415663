"""Attention layers (GQA + MLA), tensor-parallel inside shard_map.

SBP view (model axis):
  wq            S(1)   column-parallel (heads)
  wk, wv        B      replicated; each device *slices* its kv group, so the
                       kv projection is computed once per group, not per chip
  wo            S(0)   row-parallel -> output is P(sum), reduced by the caller
                       (deferred reduction, paper §3.3: residual-add happens
                       after a single psum that also covers the MLP branch
                       when profitable)

Decode uses a sequence-sharded KV cache (SBP S(seq) on the model axis): each
shard emits P(max)/P(sum) flash-decode partials combined with pmax/psum — the
paper's partial-value signature with a non-sum reduction.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention.ref import (flash_attention_ref,
                                               flash_attention_triangular)
from repro.kernels.flash_decode.ref import (combine_partials,
                                            flash_decode_partial_ref)
from repro.models.common import (MeshPlan, apply_rope, dense_init, rms_norm,
                                 split_keys, yarn_inv_freq, yarn_mscale)


# ---------------------------------------------------------------------------
# shard arithmetic
# ---------------------------------------------------------------------------

def q_heads_local(cfg: ModelConfig, plan: MeshPlan) -> int:
    return cfg.padded_heads(plan.tp) // plan.tp


def kv_heads_local(cfg: ModelConfig, plan: MeshPlan) -> int:
    tp, kv = plan.tp, cfg.num_kv_heads
    if kv >= tp:
        assert kv % tp == 0, (kv, tp)
        return kv // tp
    assert tp % kv == 0, (kv, tp)
    return 1


def _kv_slice(p_w, cfg, plan, hd):
    """Slice this device's kv-head columns out of the replicated kv weight."""
    tp, kv = plan.tp, cfg.num_kv_heads
    n_kv = kv_heads_local(cfg, plan)
    if tp == 1:
        return p_w, 0
    m = jax.lax.axis_index(plan.model_axis)
    start = (m * kv) // tp          # group-aligned for kv < tp
    w = jax.lax.dynamic_slice_in_dim(p_w, start * hd, n_kv * hd, axis=-1)
    return w, start


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, plan: MeshPlan, cross: bool = False) -> Dict:
    d, hd = cfg.d_model, cfg.head_dim
    Hp = cfg.padded_heads(plan.tp)
    KV = cfg.num_kv_heads
    ks = split_keys(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, Hp * hd)),
        "wk": dense_init(ks[1], (d, KV * hd)),
        "wv": dense_init(ks[2], (d, KV * hd)),
        "wo": dense_init(ks[3], (Hp * hd, d)),
    }
    if Hp != cfg.num_heads:  # zero the padded q heads and their wo rows
        real = cfg.num_heads * hd
        p["wq"] = p["wq"].at[:, real:].set(0.0)
        p["wo"] = p["wo"].at[real:, :].set(0.0)
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((Hp * hd,), jnp.float32)
        p["bk"] = jnp.zeros((KV * hd,), jnp.float32)
        p["bv"] = jnp.zeros((KV * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def gqa_specs(cfg: ModelConfig, plan: MeshPlan, cross: bool = False) -> Dict:
    from jax.sharding import PartitionSpec as P

    mx = plan.spec_model_axis
    p = {"wq": P(None, mx), "wk": P(), "wv": P(), "wo": P(mx, None)}
    if cfg.qkv_bias and not cross:
        p.update({"bq": P(mx), "bk": P(), "bv": P()})
    if cfg.qk_norm:
        p.update({"q_norm": P(), "k_norm": P()})
    return p


def _project_qkv(p, x, kv_src, cfg, plan, positions, kv_positions,
                 rope: bool = True):
    """q from x; k,v from kv_src (cross-attention passes encoder states)."""
    hd = cfg.head_dim
    qh = q_heads_local(cfg, plan)
    n_kv = kv_heads_local(cfg, plan)
    B, S = x.shape[0], x.shape[1]
    Skv = kv_src.shape[1]

    q = x @ p["wq"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    wk, _ = _kv_slice(p["wk"], cfg, plan, hd)
    wv, kv_start = _kv_slice(p["wv"], cfg, plan, hd)
    k = kv_src @ wk.astype(x.dtype)
    v = kv_src @ wv.astype(x.dtype)
    if "bk" in p:
        bk, _ = _kv_slice(p["bk"][None], cfg, plan, hd)
        bv, _ = _kv_slice(p["bv"][None], cfg, plan, hd)
        k = k + bk[0].astype(x.dtype)
        v = v + bv[0].astype(x.dtype)
    q = q.reshape(B, S, qh, hd)
    k = k.reshape(B, Skv, n_kv, hd)
    v = v.reshape(B, Skv, n_kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"].astype(x.dtype), cfg.norm_eps)
        k = rms_norm(k, p["k_norm"].astype(x.dtype), cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_fraction, cfg.rope_theta)
    return q, k, v


def gqa_forward(p, x, cfg: ModelConfig, plan: MeshPlan, positions,
                causal: bool = True, kv_src=None, kv_positions=None,
                sliding_window: int = 0):
    """Training/prefill attention. Returns (out_partial, (k, v)).

    ``out_partial`` is P(sum) over the model axis (row-parallel wo); caller
    reduces. (k, v) are this device's kv-head slice over the full sequence.
    """
    self_attn = kv_src is None
    kv_src = x if kv_src is None else kv_src
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _project_qkv(p, x, kv_src, cfg, plan, positions, kv_positions,
                           rope=not cfg.use_mla)
    if causal and self_attn:
        # triangular block-skipping path: half the attention FLOPs (§Perf #2)
        out = flash_attention_triangular(q, k, v,
                                         sliding_window=sliding_window)
    else:
        out = flash_attention_ref(q, k, v, causal=causal,
                                  sliding_window=sliding_window)
    B, S = x.shape[0], x.shape[1]
    out = out.reshape(B, S, -1)
    y_partial = out @ p["wo"].astype(x.dtype)     # P(sum) over model axis
    return y_partial, (k, v)


def kv_to_seq_sharded(k, v, cfg: ModelConfig, plan: MeshPlan, cache_len: int):
    """Boxing for the decode cache: S(head) -> S(seq) on the model axis.

    For kv >= tp this is the Table-2 ``S(i)->S(j)`` all_to_all; for kv < tp
    the heads are group-replicated, so the transition is the free ``B->S``
    slice (Table 2, zero cost) after a small intra-group exchange.
    Returns (B, cache_len/tp, KV, hd) local cache slices, zero-padded to
    ``cache_len`` total.
    """
    tp, KV = plan.tp, cfg.num_kv_heads
    B, S, n_kv, hd = k.shape
    L_loc = cache_len // tp

    def pad_to_cache(t):
        if S < cache_len:
            t = jnp.pad(t, ((0, 0), (0, cache_len - S), (0, 0), (0, 0)))
        return t

    if tp == 1:
        return pad_to_cache(k), pad_to_cache(v)
    ax = plan.model_axis

    if KV >= tp:
        # all_to_all: release head split, impose seq split
        def a2a(t):
            t = pad_to_cache(t)
            return jax.lax.all_to_all(t, ax, split_axis=1, concat_axis=2,
                                      tiled=True)
        return a2a(k), a2a(v)

    # kv < tp: heads are replicated within groups of tp/KV devices; gather
    # the KV distinct heads across the axis, then slice our seq chunk.
    def gather_slice(t):
        t = pad_to_cache(t)
        full = jax.lax.all_gather(t, ax, axis=2, tiled=True)  # (B, L, tp, hd)
        # deduplicate: group g of size tp/KV all computed kv head g
        group = tp // KV
        full = full.reshape(B, cache_len, KV, group, hd)[:, :, :, 0]
        m = jax.lax.axis_index(ax)
        return jax.lax.dynamic_slice_in_dim(full, m * L_loc, L_loc, axis=1)
    return gather_slice(k), gather_slice(v)


def gqa_decode(p, x, cache_k, cache_v, pos, cfg: ModelConfig, plan: MeshPlan,
               sliding_window: int = 0, cross: bool = False, enc_len: int = 0,
               cache_pos=None):
    """One-token decode over a sequence-sharded KV cache.

    x: (B, 1, d) replicated over model; cache_k/v: (B, L_loc, KV, hd);
    pos: (B,) current absolute position. ``cache_pos``: (B, L_loc) slot
    position table — when given, the cache is a RING buffer of length
    ``sliding_window`` (long-context decode) and writes go to pos % window.
    Returns (out_partial P(sum), new_cache_k, new_cache_v, new_cache_pos).
    """
    B = x.shape[0]
    hd = cfg.head_dim
    tp, KV = plan.tp, cfg.num_kv_heads
    Hp = cfg.padded_heads(tp)
    ax = plan.model_axis
    L_loc = cache_k.shape[1]

    # q for ALL heads on every device: local q heads + all_gather (tiny)
    q, k_new, v_new = _project_qkv(
        p, x, x, cfg, plan, pos[:, None], pos[:, None], rope=not cross)
    if tp > 1:
        q = jax.lax.all_gather(q, ax, axis=2, tiled=True)   # S(head)->B
    q = q[:, 0]                                             # (B, Hp, hd)

    if not cross:
        # write the new token's kv into the owning shard's slice.
        # k_new: (B, 1, n_kv, hd) is this device's kv-head group; for the
        # cache we need all KV heads — gather heads (tiny: one token).
        if tp > 1:
            kh = jax.lax.all_gather(k_new, ax, axis=2, tiled=True)
            vh = jax.lax.all_gather(v_new, ax, axis=2, tiled=True)
            if KV < tp:
                group = tp // KV
                kh = kh.reshape(B, 1, KV, group, hd)[:, :, :, 0]
                vh = vh.reshape(B, 1, KV, group, hd)[:, :, :, 0]
            else:
                kh = kh[:, :, :KV]   # heads arrive in order; groups exact
                vh = vh[:, :, :KV]
        else:
            kh, vh = k_new, v_new
        m = jax.lax.axis_index(ax) if tp > 1 else 0
        write_pos = jnp.mod(pos, sliding_window) if cache_pos is not None \
            else pos                                         # ring slot
        local_idx = write_pos - m * L_loc                    # (B,)
        owns = (local_idx >= 0) & (local_idx < L_loc)
        safe = jnp.clip(local_idx, 0, L_loc - 1)

        def write(cache, val):
            upd = jax.vmap(
                lambda c, i, u, o: jax.lax.dynamic_update_slice_in_dim(
                    c, jnp.where(o, u, jax.lax.dynamic_slice_in_dim(
                        c, i, 1, axis=0)), i, axis=0)
            )(cache, safe, val, owns)
            return upd
        cache_k = write(cache_k, kh.astype(cache_k.dtype))
        cache_v = write(cache_v, vh.astype(cache_v.dtype))
        if cache_pos is not None:
            cache_pos = write(cache_pos[..., None],
                              pos[:, None, None])[..., 0]

    # partial flash-decode over the local seq chunk
    m_idx = jax.lax.axis_index(ax) if tp > 1 else 0
    k_off = m_idx * L_loc
    mm, ll, acc = flash_decode_partial_ref(
        q, cache_k.astype(q.dtype), cache_v.astype(q.dtype),
        k_offset=k_off, cur_pos=pos if not cross else None,
        sliding_window=sliding_window,
        k_positions=cache_pos if cache_pos is not None else None)
    if cross and enc_len and enc_len < L_loc * max(tp, 1):
        pass  # cross caches are exactly enc_len; no masking needed
    if tp > 1:
        out = combine_partials(mm, ll, acc, axis_name=ax)    # P -> B
    else:
        out = combine_partials(mm[None], ll[None], acc[None])
    out = out.astype(x.dtype)                                # (B, Hp, hd)

    # row-parallel output projection: slice local heads from the combined out
    qh = Hp // tp
    if tp > 1:
        start = jax.lax.axis_index(ax) * qh
        out_loc = jax.lax.dynamic_slice_in_dim(out, start, qh, axis=1)
    else:
        out_loc = out
    y_partial = out_loc.reshape(B, 1, qh * hd) @ p["wo"].astype(x.dtype)
    return y_partial, cache_k, cache_v, cache_pos


def gqa_decode_paged(p, x, pos, cfg: ModelConfig, plan: MeshPlan, cached,
                     kv_dtype):
    """One-token decode over a cache that is read where it lies (tp = 1).

    ``cached(q)`` gives the flash-decode partial ``(m, l, acc)`` of the
    ``(B, H, hd)`` queries over the cached positions ``< pos``; the new
    token's own partial, from its K/V at the cache's ``kv_dtype``, joins it
    in :func:`combine_partials`. Returns ``(out_partial, k_new, v_new)``,
    the new position's ``(B, KV, hd)`` entries for the caller to write.
    """
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(p, x, x, cfg, plan, pos[:, None],
                                   pos[:, None])
    q = q[:, 0]                                             # (B, H, hd)
    k_new, v_new = k_new.astype(kv_dtype), v_new.astype(kv_dtype)
    own = flash_decode_partial_ref(q, k_new.astype(q.dtype),
                                   v_new.astype(q.dtype))
    m, l, acc = (jnp.stack(pair) for pair in zip(cached(q), own))
    out = combine_partials(m, l, acc).astype(x.dtype)
    y_partial = out.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return y_partial, k_new[:, 0], v_new[:, 0]


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, plan: MeshPlan) -> Dict:
    d = cfg.d_model
    H = cfg.num_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = split_keys(key, 7)
    p = {}
    if qr:
        p["wq_a"] = dense_init(ks[0], (d, qr))
        p["q_norm"] = jnp.ones((qr,), jnp.float32)
        p["wq_b"] = dense_init(ks[1], (qr, H * (nope + rope)))
    else:
        p["wq"] = dense_init(ks[0], (d, H * (nope + rope)))
    p["wkv_a"] = dense_init(ks[2], (d, r + rope))
    p["kv_norm"] = jnp.ones((r,), jnp.float32)
    p["w_uk"] = dense_init(ks[3], (r, H * nope))
    p["w_uv"] = dense_init(ks[4], (r, H * vd))
    p["wo"] = dense_init(ks[5], (H * vd, d))
    return p


def mla_specs(cfg: ModelConfig, plan: MeshPlan) -> Dict:
    from jax.sharding import PartitionSpec as P

    mx = plan.spec_model_axis
    p = {"wkv_a": P(), "kv_norm": P(),
         "w_uk": P(None, mx), "w_uv": P(None, mx), "wo": P(mx, None)}
    if cfg.q_lora_rank:
        p.update({"wq_a": P(), "q_norm": P(), "wq_b": P(None, mx)})
    else:
        p["wq"] = P(None, mx)
    return p


def _mla_rope(x, positions, cfg: ModelConfig):
    """RoPE over MLA's rope dims; DeepSeek-V2's YaRN where
    ``yarn_factor > 1`` (frequencies blended over the correction range,
    cos and sin scaled by ``mscale(f, yarn_mscale) / mscale(f,
    yarn_mscale_all_dim)``)."""
    f = cfg.yarn_factor
    if f <= 1:
        return apply_rope(x, positions, 1.0, cfg.rope_theta)
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, f,
                        cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                        cfg.yarn_beta_slow)
    scale = (yarn_mscale(f, cfg.yarn_mscale)
             / yarn_mscale(f, cfg.yarn_mscale_all_dim))
    return apply_rope(x, positions, inv_freq=inv, scale=scale)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(qk_nope + qk_rope)^-0.5``, times ``mscale(f,
    yarn_mscale_all_dim)^2`` under YaRN (1 without it)."""
    return ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2)


def _mla_q(p, x, cfg, plan, positions):
    B, S = x.shape[:2]
    H_l = cfg.num_heads // plan.tp
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = rms_norm(x @ p["wq_a"].astype(x.dtype),
                      p["q_norm"].astype(x.dtype), cfg.norm_eps)
        q = cq @ p["wq_b"].astype(x.dtype)
    else:
        q = x @ p["wq"].astype(x.dtype)
    q = q.reshape(B, S, H_l, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    return q_nope, _mla_rope(q_pe, positions, cfg)


def _mla_latent(p, x, cfg, positions):
    ckv = x @ p["wkv_a"].astype(x.dtype)                 # (B, S, r + rope)
    c = rms_norm(ckv[..., :cfg.kv_lora_rank],
                 p["kv_norm"].astype(x.dtype), cfg.norm_eps)
    k_pe = _mla_rope(ckv[..., None, cfg.kv_lora_rank:], positions,
                     cfg)[..., 0, :]                     # (B, S, rope)
    return c, k_pe


def mla_forward(p, x, cfg: ModelConfig, plan: MeshPlan, positions,
                sliding_window: int = 0):
    """Training/prefill MLA: materialize per-head k,v from the latent.
    Returns (out_partial P(sum), (c, k_pe)) — latent cache for decode."""
    B, S = x.shape[:2]
    H_l = cfg.num_heads // plan.tp
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_pe = _mla_q(p, x, cfg, plan, positions)
    c, k_pe = _mla_latent(p, x, cfg, positions)
    k_nope = (c @ p["w_uk"].astype(x.dtype)).reshape(B, S, H_l, nope)
    v = (c @ p["w_uv"].astype(x.dtype)).reshape(B, S, H_l, vd)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, H_l, rope_d))],
        axis=-1)
    with jax.named_scope("mla.attend"):
        out = flash_attention_triangular(q, k, v,
                                         sliding_window=sliding_window,
                                         sm_scale=mla_softmax_scale(cfg))
    y_partial = out.reshape(B, S, H_l * vd) @ p["wo"].astype(x.dtype)
    return y_partial, (c, k_pe)


def mla_decode(p, x, cache_c, cache_kpe, pos, cfg: ModelConfig, plan: MeshPlan,
               sliding_window: int = 0):
    """Absorbed-MLA decode: the latent cache is replicated over the model
    axis (SBP B — optimal per Table 2 since the latent is tiny), heads are
    sharded; scores are computed in latent space (absorption trick).

    x: (B, 1, d); cache_c: (B, L, r); cache_kpe: (B, L, rope).
    """
    B = x.shape[0]
    L = cache_c.shape[1]
    H_l = cfg.num_heads // plan.tp
    r = cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim

    q_nope, q_pe = _mla_q(p, x, cfg, plan, pos[:, None])
    c_new, kpe_new = _mla_latent(p, x, cfg, pos[:, None])
    # replicated cache write (every device writes the same values)
    upd = jax.vmap(lambda cc, i, u: jax.lax.dynamic_update_slice_in_dim(
        cc, u, i, axis=0))
    cache_c = upd(cache_c, pos, c_new.astype(cache_c.dtype))
    cache_kpe = upd(cache_kpe, pos, kpe_new.astype(cache_kpe.dtype))

    with jax.named_scope("mla.attend"):
        # absorbed scores: q' = q_nope @ W_uk  (per local head)
        w_uk = p["w_uk"].astype(x.dtype).reshape(r, H_l, nope)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)  # (B, H_l, r)
        s_lat = jnp.einsum("bhr,blr->bhl", q_lat, cache_c.astype(x.dtype))
        s_pe = jnp.einsum("bhe,ble->bhl", q_pe[:, 0],
                          cache_kpe.astype(x.dtype))
        s = (s_lat + s_pe).astype(jnp.float32) * mla_softmax_scale(cfg)
        kpos = jnp.arange(L)
        mask = kpos[None, :] <= pos[:, None]
        if sliding_window:
            mask &= kpos[None, :] > (pos[:, None] - sliding_window)
        s = jnp.where(mask[:, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out_lat = jnp.einsum("bhl,blr->bhr", pr, cache_c.astype(x.dtype))
        w_uv = p["w_uv"].astype(x.dtype).reshape(r, H_l, vd)
        out = jnp.einsum("bhr,rhv->bhv", out_lat, w_uv)      # (B, H_l, vd)
    y_partial = out.reshape(B, 1, H_l * vd) @ p["wo"].astype(x.dtype)
    return y_partial, cache_c, cache_kpe
