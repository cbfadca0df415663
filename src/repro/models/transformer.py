"""Model assembly: embeddings, block stacks (scan-over-periods), loss, decode.

Layout rules:

* layers are grouped into a *prologue* (unrolled; e.g. DeepSeek's leading
  dense layers) and a *body* scanned over repeating periods
  (period = lcm(attn_every, moe_every); 1 for uniform stacks, 8 for Jamba);
* every block's params for slot j are stacked over periods (leading dim
  n_periods) so the whole body is one ``lax.scan`` — keeps the HLO small for
  the 61-layer/671B dry-runs;
* activations are SBP ``(S(0) batch over data axes, B over model)``;
  attention/MLP partial outputs are P(sum) over model; the residual add
  happens after ONE psum per branch pair when both branches are partial
  (deferred reduction, paper §3.3).

Vocab-parallel embedding + the hierarchical (local-reduce) softmax
cross-entropy are the paper's Fig 11b pattern.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.softmax_xent.ref import combine_stats, local_stats_ref
from repro.models.attention import (
    gqa_decode, gqa_forward, gqa_specs, init_gqa, init_mla,
    kv_to_seq_sharded, mla_decode, mla_forward, mla_specs, q_heads_local)
from repro.models.common import (MeshPlan, certified_pmean, dense_init,
                                 force_vary, rms_norm, split_keys)
from repro.models.mamba import (
    init_mamba, mamba_decode, mamba_forward, mamba_specs)
from repro.models.mlp import (dense_mlp_forward, dense_mlp_specs, init_dense_mlp,
                              init_moe, moe_forward, moe_specs)


# ---------------------------------------------------------------------------
# layer grouping
# ---------------------------------------------------------------------------

def _period(cfg: ModelConfig) -> int:
    p = 1
    if cfg.attn_every:
        p = cfg.attn_every
    if cfg.num_experts and cfg.moe_every > 1:
        p = math.lcm(p, cfg.moe_every)
    return p


@dataclasses.dataclass(frozen=True)
class StackLayout:
    prologue: Tuple[Tuple[str, str], ...]       # (kind, mlp_kind) per layer
    period_slots: Tuple[Tuple[str, str], ...]
    n_periods: int


def stack_layout(cfg: ModelConfig) -> StackLayout:
    kinds, mlps = cfg.layer_kinds(), cfg.mlp_kinds()
    n_pro = cfg.first_dense_layers
    P = _period(cfg)
    body = cfg.num_layers - n_pro
    assert body % P == 0, (cfg.name, body, P)
    slots = tuple((kinds[n_pro + j], mlps[n_pro + j]) for j in range(P))
    # periodicity sanity: every period must repeat the slot structure
    for i in range(body // P):
        for j in range(P):
            li = n_pro + i * P + j
            assert (kinds[li], mlps[li]) == slots[j], (cfg.name, li)
    return StackLayout(tuple((kinds[i], mlps[i]) for i in range(n_pro)),
                       slots, body // P)


# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, plan: MeshPlan, kind: str, mlp_kind: str,
               cross: bool = False) -> Dict:
    d = cfg.d_model
    ks = split_keys(key, 4)
    p: Dict[str, Any] = {"ln1": jnp.ones((d,), jnp.float32)}
    if kind == "attn":
        p["attn"] = (init_mla(ks[0], cfg, plan) if cfg.use_mla
                     else init_gqa(ks[0], cfg, plan))
    else:
        p["ssm"] = init_mamba(ks[0], cfg, plan)
    if cross:
        p["ln_x"] = jnp.ones((d,), jnp.float32)
        p["xattn"] = init_gqa(ks[2], cfg, plan, cross=True)
    if mlp_kind == "dense":
        p["ln2"] = jnp.ones((d,), jnp.float32)
        p["mlp"] = init_dense_mlp(ks[1], d, cfg.d_ff)
    elif mlp_kind == "moe":
        p["ln2"] = jnp.ones((d,), jnp.float32)
        p["moe"] = init_moe(ks[1], cfg)
    return p


def block_specs(cfg: ModelConfig, plan: MeshPlan, kind: str, mlp_kind: str,
                cross: bool = False) -> Dict:
    from jax.sharding import PartitionSpec as P

    p: Dict[str, Any] = {"ln1": P()}
    if kind == "attn":
        p["attn"] = mla_specs(cfg, plan) if cfg.use_mla else gqa_specs(cfg, plan)
    else:
        p["ssm"] = mamba_specs(cfg, plan)
    if cross:
        p["ln_x"] = P()
        p["xattn"] = gqa_specs(cfg, plan, cross=True)
    if mlp_kind in ("dense", "moe"):
        p["ln2"] = P()
        p["mlp" if mlp_kind == "dense" else "moe"] = (
            dense_mlp_specs(plan) if mlp_kind == "dense"
            else moe_specs(cfg, plan))
    return p


def apply_block(p, x, cfg: ModelConfig, plan: MeshPlan, kind: str,
                mlp_kind: str, positions, causal: bool = True,
                sliding_window: int = 0, enc: Optional[jnp.ndarray] = None,
                want_cache: bool = False, cache_len: int = 0):
    """Returns (x, aux_loss, cache_or_None). x replicated over model axis.

    Branch psum outputs are tagged with ``checkpoint_name('boxed')`` so the
    remat policy can SAVE them: replaying a branch's compute in the backward
    pass is cheap, replaying its all-reduce is not (§Perf hillclimb #3)."""
    from jax.ad_checkpoint import checkpoint_name

    if plan.tp > 1:
        def psum(v):
            return checkpoint_name(jax.lax.psum(v, plan.model_axis), "boxed")
    else:
        def psum(v):
            return v
    aux = jnp.zeros((), jnp.float32)
    cache = None
    h = rms_norm(x, p["ln1"].astype(x.dtype), cfg.norm_eps)
    if kind == "attn":
        if cfg.use_mla:
            a, (c, kpe) = mla_forward(p["attn"], h, cfg, plan, positions,
                                      sliding_window)
            if want_cache:
                pad = cache_len - c.shape[1]
                cache = {"c": jnp.pad(c, ((0, 0), (0, pad), (0, 0))).astype(jnp.bfloat16),
                         "kpe": jnp.pad(kpe, ((0, 0), (0, pad), (0, 0))).astype(jnp.bfloat16)}
        else:
            a, (k, v) = gqa_forward(p["attn"], h, cfg, plan, positions,
                                    causal=causal,
                                    sliding_window=sliding_window)
            if want_cache:
                ck, cv = kv_to_seq_sharded(k.astype(jnp.bfloat16),
                                           v.astype(jnp.bfloat16), cfg, plan,
                                           cache_len)
                cache = {"k": ck, "v": cv}
        x = x + psum(a)
    else:
        if want_cache:
            a, (hstate, (tx, tbc)) = mamba_forward(p["ssm"], h, cfg, plan,
                                                   return_state=True)
            cache = {"h": hstate, "tail_x": tx, "tail_bc": tbc}
        else:
            a = mamba_forward(p["ssm"], h, cfg, plan)
        x = x + psum(a)
    if enc is not None and "xattn" in p:
        hx = rms_norm(x, p["ln_x"].astype(x.dtype), cfg.norm_eps)
        ax, (xk, xv) = gqa_forward(p["xattn"], hx, cfg, plan, positions,
                                   causal=False, kv_src=enc,
                                   kv_positions=jnp.arange(enc.shape[1]))
        if want_cache:
            cache = dict(cache or {})
            cache["xk"] = xk.astype(jnp.bfloat16)
            cache["xv"] = xv.astype(jnp.bfloat16)
        x = x + psum(ax)
    if mlp_kind == "dense":
        h2 = rms_norm(x, p["ln2"].astype(x.dtype), cfg.norm_eps)
        x = x + psum(dense_mlp_forward(p["mlp"], h2))
    elif mlp_kind == "moe":
        h2 = rms_norm(x, p["ln2"].astype(x.dtype), cfg.norm_eps)
        mo, a_aux = moe_forward(p["moe"], h2, cfg, plan)
        x = x + psum(mo)
        aux = aux + a_aux
    return x, aux, cache


def decode_block(p, x, cache, pos, cfg: ModelConfig, plan: MeshPlan,
                 kind: str, mlp_kind: str, sliding_window: int = 0):
    """Single-token step. Returns (x, new_cache)."""
    psum = (lambda v: jax.lax.psum(v, plan.model_axis)) if plan.tp > 1 \
        else (lambda v: v)
    h = rms_norm(x, p["ln1"].astype(x.dtype), cfg.norm_eps)
    new_cache = dict(cache)
    if kind == "attn":
        if cfg.use_mla:
            a, c, kpe = mla_decode(p["attn"], h, cache["c"], cache["kpe"],
                                   pos, cfg, plan, sliding_window)
            new_cache["c"], new_cache["kpe"] = c, kpe
        else:
            a, ck, cv, cp = gqa_decode(p["attn"], h, cache["k"], cache["v"],
                                       pos, cfg, plan, sliding_window,
                                       cache_pos=cache.get("pos"))
            new_cache["k"], new_cache["v"] = ck, cv
            if cp is not None:
                new_cache["pos"] = cp
        x = x + psum(a)
    else:
        a, (hs, tx, tbc) = mamba_decode(
            p["ssm"], h, (cache["h"], cache["tail_x"], cache["tail_bc"]),
            cfg, plan)
        new_cache["h"], new_cache["tail_x"], new_cache["tail_bc"] = hs, tx, tbc
        x = x + psum(a)
    if "xk" in cache:  # whisper cross-attention (static encoder cache)
        hx = rms_norm(x, p["ln_x"].astype(x.dtype), cfg.norm_eps)
        ax = _cross_attn_decode(p["xattn"], hx, cache["xk"], cache["xv"],
                                cfg, plan)
        x = x + psum(ax)
    if mlp_kind == "dense":
        h2 = rms_norm(x, p["ln2"].astype(x.dtype), cfg.norm_eps)
        x = x + psum(dense_mlp_forward(p["mlp"], h2))
    elif mlp_kind == "moe":
        h2 = rms_norm(x, p["ln2"].astype(x.dtype), cfg.norm_eps)
        mo, _ = moe_forward(p["moe"], h2, cfg, plan)
        x = x + psum(mo)
    return x, new_cache


def _cross_attn_decode(p, x, xk, xv, cfg, plan):
    """Decode-time cross attention: local q heads over the full (small)
    encoder sequence — no cache update, no seq shard."""
    from repro.kernels.flash_attention.ref import attention_dense_ref

    B = x.shape[0]
    hd = cfg.head_dim
    qh = q_heads_local(cfg, plan)
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, 1, qh, hd)
    out = attention_dense_ref(q, xk.astype(x.dtype), xv.astype(x.dtype),
                              causal=False)
    return out.reshape(B, 1, qh * hd) @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# whole model
# ---------------------------------------------------------------------------

def _stack_trees(trees: List[Any]):
    """Stack same-structured trees leaf by leaf, emptying ``trees`` as it
    goes: each per-layer leaf is released once its stack exists, so the
    stacked body and the per-layer copies are never both resident."""
    treedef = jax.tree.structure(trees[0])
    cols = [list(c) for c in zip(*(jax.tree.leaves(t) for t in trees))]
    trees.clear()
    stacked = []
    for i in range(len(cols)):
        stacked.append(jnp.stack(cols[i]))
        cols[i] = None
    return treedef.unflatten(stacked)


def init_model(key, cfg: ModelConfig, plan: MeshPlan) -> Dict:
    d, Vp = cfg.d_model, cfg.padded_vocab()
    lay = stack_layout(cfg)
    ks = split_keys(key, 8 + len(lay.prologue))
    p: Dict[str, Any] = {
        "embed": dense_init(ks[0], (Vp, d), in_axis=1),
        "unembed": dense_init(ks[1], (d, Vp)),
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    p["prologue"] = [
        init_block(ks[8 + i], cfg, plan, k, m)
        for i, (k, m) in enumerate(lay.prologue)]
    # body: stack per slot over periods
    body = []
    kb = split_keys(ks[2], max(1, lay.n_periods))
    for j, (kind, mlp_kind) in enumerate(lay.period_slots):
        per = [init_block(jax.random.fold_in(kb[i], j), cfg, plan, kind,
                          mlp_kind, cross=cfg.encoder_decoder)
               for i in range(lay.n_periods)]
        body.append(_stack_trees(per))
    p["body"] = body
    if cfg.encoder_decoder:
        enc = [init_block(jax.random.fold_in(ks[3], i), cfg, plan,
                          "attn", "dense")
               for i in range(cfg.num_encoder_layers)]
        p["enc_body"] = jax.tree.map(lambda *xs: jnp.stack(xs), *enc)
        p["enc_norm"] = jnp.ones((d,), jnp.float32)
    if cfg.mtp:
        p["mtp_norm_h"] = jnp.ones((d,), jnp.float32)
        p["mtp_norm_e"] = jnp.ones((d,), jnp.float32)
        p["mtp_proj"] = dense_init(ks[4], (2 * d, d))
        p["mtp_block"] = init_block(ks[5], cfg, plan, "attn", "dense")
    return p


def model_specs(cfg: ModelConfig, plan: MeshPlan) -> Dict:
    from jax.sharding import PartitionSpec as P

    lay = stack_layout(cfg)
    mx = plan.spec_model_axis
    p: Dict[str, Any] = {
        "embed": P(mx, None),        # vocab-parallel
        "unembed": P(None, mx),      # column-parallel logits
        "final_norm": P(),
    }
    p["prologue"] = [block_specs(cfg, plan, k, m) for (k, m) in lay.prologue]
    p["body"] = [
        jax.tree.map(lambda s: P(None, *s),   # leading period dim unsharded
                     block_specs(cfg, plan, kind, mlp_kind,
                                 cross=cfg.encoder_decoder),
                     is_leaf=lambda s: isinstance(s, P))
        for (kind, mlp_kind) in lay.period_slots]
    if cfg.encoder_decoder:
        p["enc_body"] = jax.tree.map(
            lambda s: P(None, *s), block_specs(cfg, plan, "attn", "dense"),
            is_leaf=lambda s: isinstance(s, P))
        p["enc_norm"] = P()
    if cfg.mtp:
        p.update({"mtp_norm_h": P(), "mtp_norm_e": P(),
                  "mtp_proj": P(mx, None),   # row-parallel (P(sum) output)
                  "mtp_block": block_specs(cfg, plan, "attn", "dense")})
    return p


def embed_tokens(p_embed, ids, plan: MeshPlan):
    """Vocab-parallel embedding: masked local gather -> P(sum) -> psum."""
    V_loc = p_embed.shape[0]
    if plan.tp > 1:
        m = jax.lax.axis_index(plan.model_axis)
        local = ids - m * V_loc
        ok = (local >= 0) & (local < V_loc)
        e = p_embed[jnp.clip(local, 0, V_loc - 1)]
        e = jnp.where(ok[..., None], e, 0.0)
        return jax.lax.psum(e, plan.model_axis)
    return p_embed[ids]


def lm_loss(p_unembed, h, labels, weights, plan: MeshPlan,
            cfg: ModelConfig):
    """Hierarchical sharded-vocab cross-entropy (paper Fig 11b).

    h: (B, S, d) replicated over model; labels/weights: (B, S).
    Returns mean loss over weighted tokens (still to be pmean'd over data).
    """
    B, S, d = h.shape
    logits = (h.reshape(B * S, d) @ p_unembed.astype(h.dtype))
    if plan.tp > 1:
        V_loc = p_unembed.shape[1]
        off = jax.lax.axis_index(plan.model_axis) * V_loc
        m_, s_, z_ = local_stats_ref(logits, labels.reshape(-1), off)
        tok = combine_stats(m_, s_, z_, axis_name=plan.model_axis)
    else:
        m_, s_, z_ = local_stats_ref(logits, labels.reshape(-1), 0)
        tok = combine_stats(m_[None], s_[None], z_[None])
    w = weights.reshape(-1).astype(jnp.float32)
    return jnp.sum(tok * w) / jnp.maximum(w.sum(), 1.0)


def _run_body(params, x, cfg, plan, positions, causal=True, sliding_window=0,
              enc=None, want_cache=False, cache_len=0, remat=True):
    lay = stack_layout(cfg)
    # scan carries must keep a consistent vma: force aux varying everywhere
    aux_total = force_vary((x[0, 0, 0] * 0).astype(jnp.float32),
                           plan.axis_names)
    pro_caches = []
    for p_blk, (kind, mlp_kind) in zip(params["prologue"], lay.prologue):
        x, aux, cache = apply_block(p_blk, x, cfg, plan, kind, mlp_kind,
                                    positions, causal, sliding_window, enc,
                                    want_cache, cache_len)
        aux_total += aux
        pro_caches.append(cache)

    def one_period(carry, stacked):
        x, aux = carry
        caches = []
        for j, (kind, mlp_kind) in enumerate(lay.period_slots):
            x, a, cache = apply_block(stacked[j], x, cfg, plan, kind,
                                      mlp_kind, positions, causal,
                                      sliding_window, enc, want_cache,
                                      cache_len)
            aux = aux + a
            caches.append(cache)
        return (force_vary(x, plan.axis_names),
                force_vary(aux, plan.axis_names)), caches

    if remat:
        # save the boxing-op (psum) outputs: backward recomputes the local
        # math but never re-runs the collectives
        policy = jax.checkpoint_policies.save_only_these_names("boxed")
        fn = jax.checkpoint(one_period, policy=policy)
    else:
        fn = one_period
    (x, aux_total), body_caches = jax.lax.scan(
        fn, (force_vary(x, plan.axis_names), aux_total),
        tuple(params["body"]))
    return x, aux_total, pro_caches, body_caches


def forward_loss(params, batch, cfg: ModelConfig, plan: MeshPlan,
                 remat: bool = True):
    """Training loss. batch: {"tokens": (B, S+1)} or for embed-frontend
    archs {"embeds": (B, S, d), "labels": (B, S+1...)} (+ "enc_embeds" for
    enc-dec). Returns (loss, metrics)."""
    if cfg.embed_frontend and not cfg.encoder_decoder:     # VLM
        x = batch["embeds"].astype(_adtype(cfg))
        labels = batch["labels"]
        positions = jnp.arange(x.shape[1])
        weights = jnp.ones_like(labels, jnp.float32)
    else:
        tokens = batch["tokens"]
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        positions = jnp.arange(inputs.shape[1])
        x = embed_tokens(params["embed"], inputs, plan).astype(_adtype(cfg))
        weights = jnp.ones_like(labels, jnp.float32)

    enc = None
    if cfg.encoder_decoder:
        enc = batch["enc_embeds"].astype(_adtype(cfg))
        enc_pos = jnp.arange(enc.shape[1])
        enc = enc + _sinusoid(enc.shape[1], cfg.d_model, enc.dtype)

        def enc_period(carry, p_blk):
            h, _ = carry
            h, _, _ = apply_block(p_blk, h, cfg, plan, "attn", "dense",
                                  enc_pos, causal=False)
            return (h, 0.0), None
        fn = jax.checkpoint(enc_period) if remat else enc_period
        (enc, _), _ = jax.lax.scan(fn, (enc, 0.0), params["enc_body"])
        enc = rms_norm(enc, params["enc_norm"].astype(enc.dtype), cfg.norm_eps)

    x, aux, _, _ = _run_body(params, x, cfg, plan, positions,
                             causal=True, enc=enc, remat=remat)
    # the router aux loss is computed redundantly on every model shard
    # WITHOUT a mediating psum; pmean keeps the value and makes the gradient
    # flow exactly once (cotangent 1/tp per shard, tp shards)
    aux = certified_pmean(aux, plan.model_axis)
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    loss = lm_loss(params["unembed"], x, labels, weights, plan, cfg)
    metrics = {"lm_loss": loss, "aux_loss": aux}

    if cfg.mtp:
        # MTP (DeepSeek-V3): predict t+2 from [norm(h_t); norm(emb(t+1))]
        emb_next = embed_tokens(params["embed"], labels, plan).astype(x.dtype)
        hcat = jnp.concatenate(
            [rms_norm(x, params["mtp_norm_h"].astype(x.dtype), cfg.norm_eps),
             rms_norm(emb_next, params["mtp_norm_e"].astype(x.dtype),
                      cfg.norm_eps)], axis=-1)
        # row-parallel projection: slice the (replicated) input rows to match
        # the S(0)-sharded weight, local matmul -> P(sum) -> psum
        w_mtp = params["mtp_proj"].astype(x.dtype)
        if plan.tp > 1:
            rows = w_mtp.shape[0]
            start = jax.lax.axis_index(plan.model_axis) * rows
            hcat = jax.lax.dynamic_slice_in_dim(hcat, start, rows, axis=-1)
            hm = jax.lax.psum(hcat @ w_mtp, plan.model_axis)
        else:
            hm = hcat @ w_mtp
        hm, _, _ = apply_block(params["mtp_block"], hm, cfg, plan, "attn",
                               "dense", positions)
        mtp_labels = jnp.concatenate(
            [labels[:, 1:], labels[:, -1:]], axis=1)
        mtp_w = jnp.concatenate(
            [jnp.ones_like(labels[:, 1:], jnp.float32),
             jnp.zeros_like(labels[:, -1:], jnp.float32)], axis=1)
        mtp_loss = lm_loss(params["unembed"], hm, mtp_labels, mtp_w, plan, cfg)
        metrics["mtp_loss"] = mtp_loss
        loss = loss + cfg.mtp_weight * mtp_loss

    loss = loss + cfg.router_aux_weight * aux
    metrics["loss"] = loss
    return loss, metrics


def _adtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[cfg.dtype]


def _sinusoid(length: int, d: int, dtype):
    pos = jnp.arange(length)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[None].astype(dtype)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def prefill(params, batch, cfg: ModelConfig, plan: MeshPlan, cache_len: int,
            sliding_window: int = 0):
    """Run the prompt, return (last-position logits-equivalent hidden, caches,
    positions). caches are ready for decode at position = prompt_len."""
    if cfg.embed_frontend and not cfg.encoder_decoder:
        x = batch["embeds"].astype(_adtype(cfg))
        S = x.shape[1]
    elif cfg.encoder_decoder:
        x = embed_tokens(params["embed"], batch["tokens"], plan).astype(
            _adtype(cfg))
        x = x + _sinusoid(x.shape[1], cfg.d_model, x.dtype)
        S = x.shape[1]
    else:
        x = embed_tokens(params["embed"], batch["tokens"], plan).astype(
            _adtype(cfg))
        S = x.shape[1]
    positions = jnp.arange(S)

    enc = None
    if cfg.encoder_decoder:
        enc = batch["enc_embeds"].astype(_adtype(cfg))
        enc = enc + _sinusoid(enc.shape[1], cfg.d_model, enc.dtype)
        def enc_step(carry, p_blk):
            h = carry
            h, _, _ = apply_block(p_blk, h, cfg, plan, "attn", "dense",
                                  jnp.arange(enc.shape[1]), causal=False)
            return h, None
        enc, _ = jax.lax.scan(enc_step, enc, params["enc_body"])
        enc = rms_norm(enc, params["enc_norm"].astype(enc.dtype), cfg.norm_eps)

    x, _, pro_caches, body_caches = _run_body(
        params, x, cfg, plan, positions, causal=True,
        sliding_window=sliding_window, enc=enc, want_cache=True,
        cache_len=cache_len, remat=False)
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    h_last = x[:, -1:]
    return h_last, {"prologue": pro_caches, "body": body_caches}


def decode_step(params, caches, tok, pos, cfg: ModelConfig, plan: MeshPlan,
                sliding_window: int = 0):
    """One decode step. tok: (B,) ids; pos: (B,) positions to write.
    Returns (logits_local (B, V_loc), new_caches)."""
    lay = stack_layout(cfg)
    x = embed_tokens(params["embed"], tok[:, None], plan).astype(_adtype(cfg))
    if cfg.encoder_decoder:
        # sinusoidal position for the current decode position
        d = cfg.d_model
        i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
        ang = pos[:, None].astype(jnp.float32) / jnp.power(10000.0, 2 * i / d)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = x + pe[:, None, :].astype(x.dtype)

    new_pro = []
    for p_blk, cache, (kind, mlp_kind) in zip(params["prologue"],
                                              caches["prologue"],
                                              lay.prologue):
        x, c = decode_block(p_blk, x, cache, pos, cfg, plan, kind, mlp_kind,
                            sliding_window)
        new_pro.append(c)

    def one_period(x, stacked):
        p_stk, c_stk = stacked
        new_caches = []
        for j, (kind, mlp_kind) in enumerate(lay.period_slots):
            x, c = decode_block(p_stk[j], x, c_stk[j], pos, cfg, plan,
                                kind, mlp_kind, sliding_window)
            new_caches.append(c)
        return x, new_caches

    x, new_body = jax.lax.scan(one_period, x,
                               (tuple(params["body"]), caches["body"]))
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits_local = x[:, 0] @ params["unembed"].astype(x.dtype)
    return logits_local, {"prologue": new_pro, "body": new_body}


# ---------------------------------------------------------------------------
# stack slices — the building blocks of pipelined serving (one contiguous
# chunk of the layer stack per pipeline stage, caches stage-local)
# ---------------------------------------------------------------------------

def decode_stack_slice(params, caches, x, pos, cfg: ModelConfig,
                       plan: MeshPlan, pro_kinds, sliding_window: int = 0):
    """One decode step over a slice of the stack.

    ``params``/``caches`` hold ``"prologue"`` (a list of this slice's
    unrolled blocks, kinds given by ``pro_kinds``) and ``"body"`` (per-slot
    trees stacked over this slice's periods — possibly empty). x: (B, 1, d)
    hidden entering the slice. Returns (x, new_caches); composing the slices
    in order reproduces :func:`decode_step`'s layer loop exactly.
    """
    lay = stack_layout(cfg)
    new_pro = []
    for p_blk, cache, (kind, mlp_kind) in zip(params["prologue"],
                                              caches["prologue"], pro_kinds):
        x, c = decode_block(p_blk, x, cache, pos, cfg, plan, kind, mlp_kind,
                            sliding_window)
        new_pro.append(c)
    new_body = caches["body"]
    if params["body"]:
        def one_period(x, stacked):
            p_stk, c_stk = stacked
            new_caches = []
            for j, (kind, mlp_kind) in enumerate(lay.period_slots):
                x, c = decode_block(p_stk[j], x, c_stk[j], pos, cfg, plan,
                                    kind, mlp_kind, sliding_window)
                new_caches.append(c)
            return x, new_caches
        x, new_body = jax.lax.scan(one_period, x,
                                   (tuple(params["body"]), caches["body"]))
    return x, {"prologue": new_pro, "body": new_body}


def prefill_stack_slice(params, x, positions, cfg: ModelConfig,
                        plan: MeshPlan, pro_kinds, cache_len: int,
                        sliding_window: int = 0):
    """Prefill over a slice of the stack (same structure as
    :func:`decode_stack_slice`). x: (B, S, d) hidden entering the slice.
    Returns (x, caches) with the slice's decode caches ready at position S.
    """
    lay = stack_layout(cfg)
    pro_caches = []
    for p_blk, (kind, mlp_kind) in zip(params["prologue"], pro_kinds):
        x, _, cache = apply_block(p_blk, x, cfg, plan, kind, mlp_kind,
                                  positions, True, sliding_window, None,
                                  True, cache_len)
        pro_caches.append(cache)
    body_caches = []
    if params["body"]:
        def one_period(x, stacked):
            caches = []
            for j, (kind, mlp_kind) in enumerate(lay.period_slots):
                x, _, cache = apply_block(stacked[j], x, cfg, plan, kind,
                                          mlp_kind, positions, True,
                                          sliding_window, None, True,
                                          cache_len)
                caches.append(cache)
            return force_vary(x, plan.axis_names), caches
        x, body_caches = jax.lax.scan(one_period,
                                      force_vary(x, plan.axis_names),
                                      tuple(params["body"]))
    return x, {"prologue": pro_caches, "body": body_caches}
