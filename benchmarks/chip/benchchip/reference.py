"""What the configurations' plain float32 references share: products at
``Precision.HIGHEST``, RMSNorm, the control's float8 quantizer, and the walk
from the embedding through the stacked layers to the head. Imports nothing
of the program under test."""
import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def pad(n, m=128):
    """``n`` rounded up to a multiple of ``m`` (the program's vocabulary
    rows)."""
    return -(-n // m) * m


def quantizer(lowp):
    """Identity, or a per-tensor-scaled round trip through float8 type
    ``lowp`` (the control's matmul inputs)."""
    if lowp is None:
        return lambda a: a.astype(jnp.float32)
    dt = jnp.dtype(lowp)
    top = float(jnp.finfo(dt).max)

    def q(a):
        a = a.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
        return (a / s).astype(dt).astype(jnp.float32) * s
    return q


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def logits(weights, tokens, at, layer, *, q, n_layers, eps, vocab,
           block=None):
    """Float32 logits over the first ``vocab`` ids at positions ``at``.

    ``tokens``: (B, S) int32, right-padded (causal, so padding never reaches
    an earlier position); ``at``: (B, K) positions; ``layer(p, x)`` is one
    layer, ``q`` the quantizer of the head's inputs. Runs one layer at a
    time so that only one layer's weights are upcast at once, over blocks
    of ``block`` rows (all at once by default; the last block is padded to
    the same shape, so every block runs the same programs)."""
    layer = jax.jit(layer)
    body = weights["body"][0]

    @jax.jit
    def head(x, at, fn, un):
        h = rms(jnp.take_along_axis(x, at[..., None], axis=1), fn, eps)
        return jnp.einsum("bkd,dv->bkv", q(h), q(un),
                          precision=HIGHEST)[..., :vocab]

    B = tokens.shape[0]
    block = block or B
    rows = -(-B // block) * block
    tokens = np.pad(tokens, ((0, rows - B), (0, 0)))
    at = np.pad(at, ((0, rows - B), (0, 0)))
    out = []
    for b0 in range(0, rows, block):
        x = jnp.take(weights["embed"], jnp.asarray(tokens[b0:b0 + block]),
                     axis=0).astype(jnp.float32)
        for i in range(n_layers):
            x = layer(jax.tree.map(lambda a: a[i], body), x)
        out.append(np.asarray(head(x, jnp.asarray(at[b0:b0 + block]),
                                   weights["final_norm"],
                                   weights["unembed"])))
    return np.concatenate(out)[:B]
