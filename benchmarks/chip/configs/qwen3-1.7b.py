"""Qwen3-1.7B: weights from a seed, the plain float32 reference, and the work
its math needs. Imports nothing of the program under test.

The weight tree has the layout the program's ``api.compile(params=...)``
accepts: ``embed`` (V, d), ``unembed`` (d, V), ``final_norm`` (d,), an empty
``prologue`` and one ``body`` slot whose leaves are stacked over the layers.

The reference is the decoder written out in ``jax.numpy`` at float32 with
every product at ``Precision.HIGHEST``: RMSNorm, q/k RMSNorm per head, RoPE
on interleaved channel pairs, grouped-query causal softmax attention and a
SwiGLU MLP, then the final norm and the untied head. ``lowp`` quantizes
every matmul input (weights and activations, per-tensor scale) to that
float8 type: the control that a lower precision than the configuration's
bfloat16 must fail.
"""
import math

import jax
import jax.numpy as jnp
from benchchip import reference as ref
from benchchip.reference import HIGHEST


def model_kwargs(spec):
    """``repro.configs.base.ModelConfig`` keyword arguments as run."""
    return dict(
        name=spec["name"], family="dense",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        qk_norm=True, qkv_bias=spec["attention_bias"],
        rope_theta=float(spec["rope_theta"]), norm_eps=spec["rms_norm_eps"],
        dtype=spec["torch_dtype"], tie_embeddings=spec["tie_word_embeddings"])


def _dims(spec):
    return (spec["num_hidden_layers"], spec["hidden_size"],
            spec["num_attention_heads"], spec["num_key_value_heads"],
            spec["head_dim"], spec["intermediate_size"],
            ref.pad(spec["vocab_size"]))


def init_weights(spec, key):
    """The whole weight tree in bfloat16 from ``key`` (jit this)."""
    L, d, H, KV, hd, ff, V = _dims(spec)
    ks = iter(jax.random.split(key, 16))
    bf = jnp.bfloat16

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(bf)

    def norm_w(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(bf)

    out_std = 1.0 / math.sqrt(2 * L)
    body = {
        "ln1": norm_w((L, d)), "ln2": norm_w((L, d)),
        "attn": {"wq": normal((L, d, H * hd), d ** -0.5),
                 "wk": normal((L, d, KV * hd), d ** -0.5),
                 "wv": normal((L, d, KV * hd), d ** -0.5),
                 "wo": normal((L, H * hd, d), (H * hd) ** -0.5 * out_std),
                 "q_norm": norm_w((L, hd)), "k_norm": norm_w((L, hd))},
        "mlp": {"w_gate": normal((L, d, ff), d ** -0.5),
                "w_up": normal((L, d, ff), d ** -0.5),
                "w_down": normal((L, ff, d), ff ** -0.5 * out_std)},
    }
    return {"embed": normal((V, d), 1.0), "unembed": normal((d, V), d ** -0.5),
            "final_norm": norm_w((d,)), "prologue": [], "body": [body]}


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def _rope(x, theta):
    """x: (B, S, heads, hd) at positions 0..S-1; pairs (2i, 2i+1)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _layer(p, x, spec, q):
    _, d, H, KV, hd, _, _ = _dims(spec)
    eps = spec["rms_norm_eps"]
    B, S, _ = x.shape
    a = p["attn"]

    def mm(u, w):
        return jnp.einsum("bsi,io->bso", q(u), q(w), precision=HIGHEST)

    h = ref.rms(x, p["ln1"], eps)
    qh = ref.rms(mm(h, a["wq"]).reshape(B, S, H, hd), a["q_norm"], eps)
    kh = ref.rms(mm(h, a["wk"]).reshape(B, S, KV, hd), a["k_norm"], eps)
    vh = mm(h, a["wv"]).reshape(B, S, KV, hd)
    qh, kh = _rope(qh, spec["rope_theta"]), _rope(kh, spec["rope_theta"])
    kh = jnp.repeat(kh, H // KV, axis=2)
    vh = jnp.repeat(vh, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(kh),
                   precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q(w), q(vh),
                   precision=HIGHEST).reshape(B, S, H * hd)
    x = x + mm(o, a["wo"])
    h2 = ref.rms(x, p["ln2"], eps)
    m = p["mlp"]
    g = jax.nn.silu(mm(h2, m["w_gate"])) * mm(h2, m["w_up"])
    return x + mm(g, m["w_down"])


def reference_logits(spec, weights, tokens, at, *, lowp=None,
                     block=None):
    """Float32 logits over the real vocabulary at positions ``at``
    (:func:`benchchip.reference.logits`)."""
    q = ref.quantizer(lowp)
    return ref.logits(weights, tokens, at, lambda p, x: _layer(p, x, spec, q),
                      q=q, n_layers=spec["num_hidden_layers"],
                      eps=spec["rms_norm_eps"], vocab=spec["vocab_size"],
                      block=block)


# ---------------------------------------------------------------------------
# the work the math needs
# ---------------------------------------------------------------------------

def matmul_params(spec):
    """Weights one token multiplies by: every layer's projections and the
    head (the embedding is a row lookup)."""
    L, d, H, KV, hd, ff, _ = _dims(spec)
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return L * per_layer + d * spec["vocab_size"]


def weight_bytes(spec, itemsize=2):
    """Bytes of the weights a decode step reads once: every layer's
    matrices and norms, the final norm and the head, at the served dtype."""
    L, d, _, _, hd, _, _ = _dims(spec)
    norms = L * (2 * d + 2 * hd) + d
    return (matmul_params(spec) + norms) * itemsize


def kv_bytes_per_token(spec, itemsize=2):
    L, _, _, KV, hd, _, _ = _dims(spec)
    return L * 2 * KV * hd * itemsize


def token_flops(spec, ctx):
    """FLOPs of one token attending over ``ctx`` positions (itself
    included): 2 per multiply-add of every projection, plus q.k and p.v."""
    L, _, H, _, hd, _, _ = _dims(spec)
    return 2 * matmul_params(spec) + L * 4 * H * hd * ctx


def prefill_flops(spec, n):
    """FLOPs of a causal prefill of ``n`` prompt tokens, the head applied
    at the last position only."""
    L, d, H, _, hd, _, _ = _dims(spec)
    head = d * spec["vocab_size"]
    return (2 * (matmul_params(spec) - head) * n + 2 * head
            + L * 2 * H * hd * n * (n + 1))


def decode_work(spec, ctxs):
    """(FLOPs, bytes) one decode step needs for live slots whose attention
    spans ``ctxs`` positions each: the weights once, every live slot's KV
    at its live positions, the new token's KV written, and the embedding
    rows and logits of the live slots."""
    d = spec["hidden_size"]
    flops = sum(token_flops(spec, c) for c in ctxs)
    kv = kv_bytes_per_token(spec)
    nbytes = (weight_bytes(spec) + sum(ctxs) * kv + len(ctxs) * kv
              + len(ctxs) * (2 * d + 4 * spec["vocab_size"]))
    return flops, nbytes
