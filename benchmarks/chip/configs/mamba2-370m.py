"""Mamba2-370m: weights from a seed, the plain float32 reference, and the
work its math needs. Imports nothing of the program under test.

The weight tree has the layout the program's ``api.compile(params=...)``
accepts: ``embed`` (V, d), ``unembed`` (d, V), ``final_norm`` (d,), an empty
``prologue`` and one ``body`` slot whose leaves are stacked over the layers.

The reference is the Mamba-2 block written out in ``jax.numpy`` at float32,
products at ``Precision.HIGHEST``: RMSNorm, the input projections, causal
depthwise convolutions with SiLU, ``dt = softplus(dt + dt_bias)``, the SSM
recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``,
``y_t = C_t h_t + D x_t`` stepped one position at a time, the gated RMSNorm
``rms(y * silu(z))`` and the output projection; then the final norm and the
untied head. ``lowp`` quantizes every matmul input to that float8 type (the
control); the recurrence stays float32.
"""
import math

import jax
import jax.numpy as jnp
from benchchip import reference as ref
from benchchip.reference import HIGHEST


def model_kwargs(spec):
    """``repro.configs.base.ModelConfig`` keyword arguments as run."""
    s = spec["ssm_cfg"]
    return dict(
        name=spec["name"], family="ssm", num_layers=spec["n_layer"],
        d_model=spec["d_model"], num_heads=0, num_kv_heads=0,
        head_dim=s["headdim"], d_ff=spec["d_intermediate"],
        vocab_size=spec["vocab_size"], ssm_d_state=s["d_state"],
        ssm_d_conv=s["d_conv"], ssm_expand=s["expand"],
        ssm_head_dim=s["headdim"], ssm_chunk=s["chunk_size"],
        norm_eps=spec["norm_epsilon"], dtype=spec["torch_dtype"],
        tie_embeddings=spec["tie_embeddings"])


def _dims(spec):
    s = spec["ssm_cfg"]
    d = spec["d_model"]
    di = s["expand"] * d
    return (spec["n_layer"], d, di, di // s["headdim"], s["headdim"],
            s["d_state"], s["d_conv"], ref.pad(spec["vocab_size"]))


def init_weights(spec, key):
    """The whole weight tree in bfloat16 from ``key`` (jit this)."""
    L, d, di, H, P, N, K, V = _dims(spec)
    s = spec["ssm_cfg"]
    ks = iter(jax.random.split(key, 16))
    bf = jnp.bfloat16

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(bf)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    def norm_w(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(bf)

    lo, hi = s["A_init_range"]
    dt = jnp.exp(uniform((L, H), math.log(s["dt_min"]),
                         math.log(s["dt_max"])))
    ssm = {"w_x": normal((L, d, di), d ** -0.5),
           "w_z": normal((L, d, di), d ** -0.5),
           "w_bc": normal((L, d, 2 * N), d ** -0.5),
           "w_dt": normal((L, d, H), d ** -0.5),
           "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(bf),
           "A_log": jnp.log(uniform((L, H), lo, hi)).astype(bf),
           "D": jnp.ones((L, H), bf),
           "conv_x": normal((L, di, K), K ** -0.5),
           "conv_bc": normal((L, 2 * N, K), K ** -0.5),
           "norm_w": norm_w((L, di)),
           "out_proj": normal((L, di, d), di ** -0.5 / math.sqrt(2 * L))}
    body = {"ln1": norm_w((L, d)), "ssm": ssm}
    return {"embed": normal((V, d), 1.0), "unembed": normal((d, V), d ** -0.5),
            "final_norm": norm_w((d,)), "prologue": [], "body": [body]}


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def _conv(u, w):
    """Causal depthwise convolution: u (B, S, C), w (C, K); output t sees
    u[t-K+1 .. t] with taps w[:, 0 .. K-1]."""
    K = w.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    S = u.shape[1]
    w = w.astype(jnp.float32)
    return sum(up[:, i:i + S] * w[None, None, :, i] for i in range(K))


def _layer(p, x, spec, q):
    L, d, di, H, P, N, K, V = _dims(spec)
    eps = spec["norm_epsilon"]
    B, S, _ = x.shape
    m = p["ssm"]

    def mm(u, w):
        return jnp.einsum("bsi,io->bso", q(u), q(w), precision=HIGHEST)

    h = ref.rms(x, p["ln1"], eps)
    xs = jax.nn.silu(_conv(mm(h, m["w_x"]), m["conv_x"]))
    bc = jax.nn.silu(_conv(mm(h, m["w_bc"]), m["conv_bc"]))
    z = mm(h, m["w_z"])
    dt = jax.nn.softplus(mm(h, m["w_dt"]) + m["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(m["A_log"].astype(jnp.float32))
    Bm, Cm = bc[..., :N], bc[..., N:]
    xh = xs.reshape(B, S, H, P)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp              # (B,H,P) (B,H) (B,N) (B,N)
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t, precision=HIGHEST)
        return state, y_t

    h0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (xh.transpose(1, 0, 2, 3),
                                    dt.transpose(1, 0, 2),
                                    Bm.transpose(1, 0, 2),
                                    Cm.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2, 3) + xh * m["D"].astype(jnp.float32)[:, None]
    y = ref.rms(y.reshape(B, S, di) * jax.nn.silu(z), m["norm_w"], eps)
    return x + mm(y, m["out_proj"])


def reference_logits(spec, weights, tokens, at, *, lowp=None,
                     block=None):
    """Float32 logits over the real vocabulary at positions ``at``
    (:func:`benchchip.reference.logits`)."""
    q = ref.quantizer(lowp)
    return ref.logits(weights, tokens, at, lambda p, x: _layer(p, x, spec, q),
                      q=q, n_layers=spec["n_layer"], eps=spec["norm_epsilon"],
                      vocab=spec["vocab_size"], block=block)


# ---------------------------------------------------------------------------
# the work the math needs
# ---------------------------------------------------------------------------

def matmul_params(spec):
    """Weights one token multiplies by: the input and output projections
    of every layer and the head (the embedding is a row lookup)."""
    L, d, di, H, P, N, K, V = _dims(spec)
    return L * (d * (2 * di + 2 * N + H) + di * d) + d * spec["vocab_size"]


def weight_bytes(spec, itemsize=2):
    """Bytes of the weights a decode step reads once: projections, the
    convolutions, the per-head and per-channel vectors, norms and head."""
    L, d, di, H, P, N, K, V = _dims(spec)
    small = L * ((di + 2 * N) * K + 3 * H + di + d) + d
    return (matmul_params(spec) + small) * itemsize


def state_bytes(spec):
    """Recurrent state of one sequence: the float32 SSM state and the
    bfloat16 convolution tails of every layer."""
    L, d, di, H, P, N, K, V = _dims(spec)
    return L * (H * P * N * 4 + (K - 1) * (di + 2 * N) * 2)


def token_flops(spec, ctx=None):
    """FLOPs of one token (independent of the context): 2 per multiply-add
    of every projection, the depthwise convolutions, and the recurrence
    (3 per state element to decay and add, 2 to read out)."""
    L, d, di, H, P, N, K, V = _dims(spec)
    return (2 * matmul_params(spec)
            + L * (2 * K * (di + 2 * N) + 5 * H * P * N))


def prefill_flops(spec, n):
    """FLOPs of a prefill of ``n`` prompt tokens, the head applied at the
    last position only (the recurrence's count: a chunked scan that does
    more arithmetic is not credited for it)."""
    head = spec["d_model"] * spec["vocab_size"]
    return (token_flops(spec) - 2 * head) * n + 2 * head


def decode_work(spec, ctxs):
    """(FLOPs, bytes) one decode step needs for the live slots ``ctxs``
    (their context lengths do not change the work): the weights once, each
    live slot's recurrent state read and written, and its embedding row and
    logits."""
    d = spec["d_model"]
    n = len(ctxs)
    return (n * token_flops(spec),
            weight_bytes(spec) + n * (2 * state_bytes(spec) + 2 * d
                                      + 4 * spec["vocab_size"]))
