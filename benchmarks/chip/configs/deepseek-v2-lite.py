"""DeepSeek-V2-Lite, the chip's share of a four-chip expert-parallel layer:
weights from a seed, the plain float32 reference, and the work its math
needs. Imports nothing of the program under test.

The weight tree has the layout the program's ``api.compile(params=...)``
accepts: ``embed`` (V, d), ``unembed`` (d, V), ``final_norm`` (d,), one
``prologue`` block (layer 0: latent attention and the dense MLP) and one
``body`` slot whose leaves are stacked over the 26 expert layers (latent
attention, the router over all 64 experts, the 16 routed experts held here
and the 2 shared experts as one MLP of twice the width).

The reference is the model written out in ``jax.numpy`` at float32 with
every product at ``Precision.HIGHEST``: RMSNorm; multi-head latent
attention with the per-head keys and values expanded from the latent (no
absorption), DeepSeek-V2's YaRN rope on the rope dims and its softmax
scale; the router's softmax over all 64 experts, the top 6, the gates not
renormalised; experts 0-15 and the shared experts; then the final norm and
the untied head. ``lowp`` quantizes every matmul input (weights and
activations, per-tensor scale) to that float8 type: the control that a
lower precision than the configuration's bfloat16 must fail.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
from benchchip import reference as ref
from benchchip.reference import HIGHEST


def _yarn(spec):
    r = spec["rope_scaling"]
    return (float(r["factor"]), r["original_max_position_embeddings"],
            float(r["beta_fast"]), float(r["beta_slow"]), float(r["mscale"]),
            float(r["mscale_all_dim"]))


def model_kwargs(spec):
    """``repro.configs.base.ModelConfig`` keyword arguments as run."""
    dep = spec["deployment"]
    f, orig, fast, slow, ms, ms_all = _yarn(spec)
    return dict(
        name=spec["name"], family="moe",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        rope_theta=float(spec["rope_theta"]), use_mla=True,
        kv_lora_rank=spec["kv_lora_rank"],
        q_lora_rank=spec["q_lora_rank"] or 0,
        qk_nope_head_dim=spec["qk_nope_head_dim"],
        qk_rope_head_dim=spec["qk_rope_head_dim"],
        v_head_dim=spec["v_head_dim"],
        yarn_factor=f, yarn_original_max_pos=orig, yarn_beta_fast=fast,
        yarn_beta_slow=slow, yarn_mscale=ms, yarn_mscale_all_dim=ms_all,
        num_experts=dep["published_n_routed_experts"],
        experts_held=spec["n_routed_experts"],
        first_expert=dep["first_expert"],
        num_shared_experts=spec["n_shared_experts"],
        top_k=spec["num_experts_per_tok"],
        norm_topk_prob=spec["norm_topk_prob"],
        routed_scaling_factor=float(spec["routed_scaling_factor"]),
        moe_d_ff=spec["moe_intermediate_size"],
        first_dense_layers=spec["first_k_dense_replace"],
        norm_eps=spec["rms_norm_eps"], dtype=spec["torch_dtype"],
        tie_embeddings=spec["tie_word_embeddings"])


def _dims(spec):
    """(layers, d, heads, nope, rope, v, latent, dense ff, expert ff,
    experts held, shared experts, experts routed over, experts a token,
    padded vocab)."""
    return (spec["num_hidden_layers"], spec["hidden_size"],
            spec["num_attention_heads"], spec["qk_nope_head_dim"],
            spec["qk_rope_head_dim"], spec["v_head_dim"],
            spec["kv_lora_rank"], spec["intermediate_size"],
            spec["moe_intermediate_size"], spec["n_routed_experts"],
            spec["n_shared_experts"],
            spec["deployment"]["published_n_routed_experts"],
            spec["num_experts_per_tok"], ref.pad(spec["vocab_size"]))


def init_weights(spec, key):
    """The whole weight tree in bfloat16 from ``key`` (jit this). Stacked
    leaves are drawn one layer at a time (``lax.map``), so no float32 copy
    of a whole stacked expert leaf is ever made."""
    L, d, H, nope, rope, vd, r, ff, eff, E, S, _, _, V = _dims(spec)
    n_moe = L - spec["first_k_dense_replace"]
    ks = iter(jax.random.split(key, 32))
    bf = jnp.bfloat16
    out_std = 1.0 / math.sqrt(2 * L)

    def normal(shape, std, stack=0):
        def one(k):
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(bf)
        k = next(ks)
        return jax.lax.map(one, jax.random.split(k, stack)) if stack \
            else one(k)

    def norm_w(shape, stack=0):
        return (1.0 + 0.1 * jax.random.normal(
            next(ks), ((stack,) if stack else ()) + shape, jnp.float32)
        ).astype(bf)

    def attn(n=0):
        return {"wq": normal((d, H * (nope + rope)), d ** -0.5, n),
                "wkv_a": normal((d, r + rope), d ** -0.5, n),
                "kv_norm": norm_w((r,), n),
                "w_uk": normal((r, H * nope), r ** -0.5, n),
                "w_uv": normal((r, H * vd), r ** -0.5, n),
                "wo": normal((H * vd, d), (H * vd) ** -0.5 * out_std, n)}

    def mlp(width, n=0):
        return {"w_gate": normal((d, width), d ** -0.5, n),
                "w_up": normal((d, width), d ** -0.5, n),
                "w_down": normal((width, d), width ** -0.5 * out_std, n)}

    layer0 = {"ln1": norm_w((d,)), "attn": attn(), "ln2": norm_w((d,)),
              "mlp": mlp(ff)}
    experts = {"w_gate": normal((E, d, eff), d ** -0.5, n_moe),
               "w_up": normal((E, d, eff), d ** -0.5, n_moe),
               "w_down": normal((E, eff, d), eff ** -0.5 * out_std, n_moe)}
    body = {"ln1": norm_w((d,), n_moe), "attn": attn(n_moe),
            "ln2": norm_w((d,), n_moe),
            "moe": {"router": normal(
                (d, spec["deployment"]["published_n_routed_experts"]),
                d ** -0.5, n_moe),
                **experts, "shared": mlp(S * eff, n_moe)}}
    return {"embed": normal((V, d), 1.0), "unembed": normal((d, V), d ** -0.5),
            "final_norm": norm_w((d,)), "prologue": [layer0], "body": [body]}


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_tables(spec, S):
    """cos and sin (S, rope) of DeepSeek-V2's YaRN rotary embedding, as its
    ``DeepseekV2YarnRotaryEmbedding`` builds them."""
    dim, base = spec["qk_rope_head_dim"], float(spec["rope_theta"])
    f, orig, fast, slow, ms, ms_all = _yarn(spec)

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(corr_dim(fast)), 0)
    high = min(math.ceil(corr_dim(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (f * base ** (np.arange(0, dim, 2, dtype=np.float32)
                                / dim))
    keep = 1.0 - ramp
    inv = inter * (1 - keep) + extra * keep
    freqs = np.outer(np.arange(S, dtype=np.float32), inv)
    emb = np.concatenate([freqs, freqs], -1)
    scale = _yarn_mscale(f, ms) / _yarn_mscale(f, ms_all)
    return (jnp.asarray(np.cos(emb) * scale, jnp.float32),
            jnp.asarray(np.sin(emb) * scale, jnp.float32))


def _rope(x, cos, sin):
    """x: (B, S, heads, rope). The published code's order: channels
    de-interleaved into halves, then the halves rotated."""
    B, S, h, dim = x.shape
    x = x.reshape(B, S, h, dim // 2, 2).swapaxes(-1, -2).reshape(B, S, h, dim)
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos[None, :, None] + rot * sin[None, :, None]


def _attn(a, h, spec, q, mm, cos, sin):
    _, _, H, nope, rope, vd, r, _, _, _, _, _, _, _ = _dims(spec)
    eps = spec["rms_norm_eps"]
    B, S, _ = h.shape
    qh = mm(h, a["wq"]).reshape(B, S, H, nope + rope)
    ckv = mm(h, a["wkv_a"])
    c = ref.rms(ckv[..., :r], a["kv_norm"], eps)
    k_pe = _rope(ckv[..., None, r:], cos, sin)                  # (B,S,1,rope)
    k_nope = mm(c, a["w_uk"]).reshape(B, S, H, nope)
    v = mm(c, a["w_uv"]).reshape(B, S, H, vd)
    qf = jnp.concatenate([qh[..., :nope], _rope(qh[..., nope:], cos, sin)],
                         -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, rope))],
                         -1)
    f, _, _, _, _, ms_all = _yarn(spec)
    scale = (nope + rope) ** -0.5 * _yarn_mscale(f, ms_all) ** 2
    s = jnp.einsum("bqhd,bkhd->bhqk", q(qf), q(kf), precision=HIGHEST) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q(w), q(v),
                   precision=HIGHEST).reshape(B, S, H * vd)
    return mm(o, a["wo"])


def _swiglu(m, h, mm):
    return mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]), m["w_down"])


def _experts(m, h, spec, q, mm):
    """The router over every expert; the held experts' part and the shared
    experts."""
    _, _, _, _, _, _, _, _, _, E, _, _, K, _ = _dims(spec)
    first = spec["deployment"]["first_expert"]
    probs = jax.nn.softmax(mm(h, m["router"]), axis=-1)         # (B,S,64)
    top, idx = jax.lax.top_k(probs, K)
    if spec["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    top = top * spec["routed_scaling_factor"]
    out = _swiglu(m["shared"], h, mm)
    for e in range(E):
        gate = jnp.sum(jnp.where(idx == first + e, top, 0.0), -1)  # (B,S)
        w = {k: m[k][e] for k in ("w_gate", "w_up", "w_down")}
        out = out + gate[..., None] * _swiglu(w, h, mm)
    return out


def _layer(p, x, spec, q, cos, sin):
    eps = spec["rms_norm_eps"]

    def mm(u, w):
        return jnp.einsum("bsi,io->bso", q(u), q(w), precision=HIGHEST)

    x = x + _attn(p["attn"], ref.rms(x, p["ln1"], eps), spec, q, mm, cos, sin)
    h2 = ref.rms(x, p["ln2"], eps)
    if "mlp" in p:
        return x + _swiglu(p["mlp"], h2, mm)
    return x + _experts(p["moe"], h2, spec, q, mm)


def reference_logits(spec, weights, tokens, at, *, lowp=None, block=None):
    """Float32 logits over the real vocabulary at positions ``at``:
    ``tokens`` (B, S) int32 right-padded, ``at`` (B, K). One layer's weights
    are upcast at a time, over blocks of ``block`` rows (all at once by
    default; the last block is padded, so every block runs the same
    programs)."""
    q = ref.quantizer(lowp)
    eps = spec["rms_norm_eps"]
    V = spec["vocab_size"]
    cos, sin = _rope_tables(spec, tokens.shape[1])
    layer = jax.jit(lambda p, x: _layer(p, x, spec, q, cos, sin))
    body = weights["body"][0]
    n_moe = spec["num_hidden_layers"] - len(weights["prologue"])

    @jax.jit
    def head(x, at, fn, un):
        h = ref.rms(jnp.take_along_axis(x, at[..., None], axis=1), fn, eps)
        return jnp.einsum("bkd,dv->bkv", q(h), q(un),
                          precision=HIGHEST)[..., :V]

    B = tokens.shape[0]
    block = block or B
    rows = -(-B // block) * block
    tokens = np.pad(tokens, ((0, rows - B), (0, 0)))
    at = np.pad(at, ((0, rows - B), (0, 0)))
    out = []
    for b0 in range(0, rows, block):
        x = jnp.take(weights["embed"], jnp.asarray(tokens[b0:b0 + block]),
                     axis=0).astype(jnp.float32)
        for p in weights["prologue"]:
            x = layer(p, x)
        for i in range(n_moe):
            x = layer(jax.tree.map(lambda a: a[i], body), x)
        out.append(np.asarray(head(x, jnp.asarray(at[b0:b0 + block]),
                                   weights["final_norm"],
                                   weights["unembed"])))
    return np.concatenate(out)[:B]


# ---------------------------------------------------------------------------
# the work the math needs
# ---------------------------------------------------------------------------

def _attn_params(spec):
    _, d, H, nope, rope, vd, r, _, _, _, _, _, _, _ = _dims(spec)
    return (d * H * (nope + rope) + d * (r + rope) + r * H * nope
            + r * H * vd + H * vd * d)


def expert_params(spec):
    """One routed expert of one layer: gate, up and down."""
    _, d, _, _, _, _, _, _, eff, _, _, _, _, _ = _dims(spec)
    return 3 * d * eff


def matmul_params(spec):
    """Weights one token multiplies by: every layer's projections, layer
    0's dense MLP, each expert layer's router, shared experts and the held
    experts the token is routed to (``experts a token * held / routed``
    of them on average), and the head (the embedding is a row lookup)."""
    L, d, _, _, _, _, _, ff, eff, E, S, E_all, K, _ = _dims(spec)
    n_moe = L - spec["first_k_dense_replace"]
    moe = d * E_all + S * 3 * d * eff + K * E * expert_params(spec) // E_all
    return (L * _attn_params(spec) + 3 * d * ff * (L - n_moe)
            + n_moe * moe + d * spec["vocab_size"])


def weight_bytes(spec, itemsize=2):
    """Bytes of the weights a decode step reads once whatever the routing:
    every matrix and norm but the routed experts, the final norm and the
    head, at the served dtype."""
    L, d, _, _, _, _, r, ff, eff, _, S, E_all, _, _ = _dims(spec)
    n_moe = L - spec["first_k_dense_replace"]
    mats = (L * _attn_params(spec) + 3 * d * ff * (L - n_moe)
            + n_moe * (d * E_all + S * 3 * d * eff) + d * spec["vocab_size"])
    norms = L * (2 * d + r) + d
    return (mats + norms) * itemsize


def touched_experts(spec, n):
    """Held experts one expert layer is expected to read for ``n`` tokens
    under uniform routing: ``held * (1 - (1 - a token's / routed)^n)``."""
    _, _, _, _, _, _, _, _, _, E, _, E_all, K, _ = _dims(spec)
    return E * (1.0 - (1.0 - K / E_all) ** n)


def latent_bytes_per_token(spec, itemsize=2):
    """The latent cache of one position: ``c`` and ``k_pe``, every layer."""
    L, _, _, _, rope, _, r, _, _, _, _, _, _, _ = _dims(spec)
    return L * (r + rope) * itemsize


def token_flops(spec, ctx):
    """FLOPs of one token attending over ``ctx`` positions (itself
    included): 2 per multiply-add of every projection, plus q.k over the
    nope and rope dims and p.v, per head."""
    L, _, H, nope, rope, vd, _, _, _, _, _, _, _, _ = _dims(spec)
    return 2 * matmul_params(spec) + L * 2 * H * (nope + rope + vd) * ctx


def prefill_flops(spec, n):
    """FLOPs of a causal prefill of ``n`` prompt tokens, the head applied
    at the last position only."""
    L, d, H, nope, rope, vd, _, _, _, _, _, _, _, _ = _dims(spec)
    head = d * spec["vocab_size"]
    return (2 * (matmul_params(spec) - head) * n + 2 * head
            + L * H * (nope + rope + vd) * n * (n + 1))


def decode_work(spec, ctxs, itemsize=2):
    """(FLOPs, bytes) one decode step needs for live slots whose attention
    spans ``ctxs`` positions each: the weights outside the routed experts
    once, the held experts the live tokens are expected to touch in every
    expert layer, every live slot's latent cache at its live positions, the
    new positions written, and the embedding rows and logits of the live
    slots."""
    L, d = spec["num_hidden_layers"], spec["hidden_size"]
    n_moe = L - spec["first_k_dense_replace"]
    n = len(ctxs)
    flops = sum(token_flops(spec, c) for c in ctxs)
    lat = latent_bytes_per_token(spec, itemsize)
    experts = (n_moe * touched_experts(spec, n) * expert_params(spec)
               * itemsize)
    nbytes = (weight_bytes(spec, itemsize) + experts + sum(ctxs) * lat
              + n * lat + n * (2 * d + 4 * spec["vocab_size"]))
    return flops, nbytes
