"""Temperature/top-k/top-p sampling as an actor-borne RNG register stream.

The sampler state is ONE ``jax.random`` key carried by the actor that owns
the decode head (the last stage actor under ``backend="actors"``, the
inline engine under ``backend="monolithic"``) — the same persistent-state
pattern as the AdamW moments in training pipelines. Every work item that
produces tokens (a prefill, a decode round, the *final* chunk of a chunked
prefill) splits the stream exactly once, and slots inside the item fold
their slot index into the subkey. Because both backends drive the
identical round structure and the last stage fires in FIFO submission
order, a fixed seed yields token-identical streams across actors and
monolithic — sampled decode is as reproducible as greedy.

``temperature == 0`` delegates to the existing
:func:`repro.train.steps.greedy_from_logits` verbatim, so greedy sampling
is bitwise-identical to the default (no-sampler) path.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class SamplingSpec:
    """Declarative sampling knobs for ``api.compile(..., sampling=)``.

    ``temperature=0`` is exact greedy; ``top_k=0`` / ``top_p=1.0`` disable
    the respective filters. ``seed`` seeds the actor-borne key stream."""

    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(
                f"temperature={self.temperature} must be >= 0 "
                "(0 = greedy)")
        if not isinstance(self.top_k, int) or self.top_k < 0:
            raise ValueError(f"top_k={self.top_k!r} must be an int >= 0 "
                             "(0 = disabled)")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p={self.top_p} must be in (0, 1] "
                             "(1.0 = disabled)")
        if not isinstance(self.seed, int):
            raise ValueError(f"seed={self.seed!r} must be an int")


def _build_sample_fn(spec: SamplingSpec, vocab_size: int):
    """Jit the per-round sampling program: mask the padded-vocab columns
    (same mask as ``greedy_from_logits``), apply temperature, top-k, then
    top-p (nucleus, always keeping the most likely token), and draw one
    categorical sample per slot with a per-slot folded key."""
    import jax
    import jax.numpy as jnp

    t, k, p = spec.temperature, spec.top_k, spec.top_p

    def one(key, logits):
        vp = logits.shape[-1]
        z = jnp.where(jnp.arange(vp) >= vocab_size, -jnp.inf,
                      logits.astype(jnp.float32))
        z = z / t
        if 0 < k < vocab_size:
            kth = jax.lax.top_k(z, k)[0][-1]
            z = jnp.where(z < kth, -jnp.inf, z)
        if p < 1.0:
            sz = jnp.sort(z)[::-1]
            probs = jax.nn.softmax(sz)
            keep = jnp.cumsum(probs) - probs < p     # top-1 always kept
            thr = jnp.min(jnp.where(keep, sz, jnp.inf))
            z = jnp.where(z < thr, -jnp.inf, z)
        return jax.random.categorical(key, z).astype(jnp.int32)

    def batch(key, logits):
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(logits.shape[0]))
        return jax.vmap(one)(keys, logits)

    return jax.jit(batch)


class SamplerStream:
    """The persistent sampler register: a key split once per sampled work
    item. Lives in the last stage actor's closure or in the inline
    engine."""

    def __init__(self, spec: SamplingSpec, vocab_size: int):
        import jax

        self.spec = spec
        self.vocab_size = vocab_size
        self.key = jax.random.PRNGKey(spec.seed)
        self._fn = (None if spec.temperature == 0
                    else _build_sample_fn(spec, vocab_size))

    def sample(self, logits):
        """Draw one token per row of ``(B, padded_vocab)`` logits, advancing
        the key stream. ``temperature == 0`` is exact greedy — bitwise the
        existing ``greedy_from_logits`` path (the stream still advances so
        greedy and sampled sessions consume keys identically)."""
        import jax

        self.key, sub = jax.random.split(self.key)
        if self._fn is None:
            from repro.train.steps import greedy_from_logits

            return greedy_from_logits(logits, self.vocab_size)
        return self._fn(sub, logits)
