"""MLP layers: dense SwiGLU (tensor-parallel) and MoE (expert-parallel).

SBP view (model axis):
  dense:  w_gate/w_up S(1) column-parallel, w_down S(0) row-parallel ->
          output P(sum), reduced by the caller.
  moe:    experts S(0) on the *expert* dimension (expert parallelism);
          each device routes the (replicated) token set over all experts and
          computes every pair routed to its own, with no capacity and no
          drop — the combine is P(sum) over the model axis. Shared experts are a
          dense row-parallel MLP whose partial output is summed into the same
          P(sum) before a single psum (deferred reduction, paper §3.3).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import MeshPlan, dense_init, split_keys, swiglu


# ---------------------------------------------------------------------------
# dense SwiGLU
# ---------------------------------------------------------------------------

def init_dense_mlp(key, d_model: int, d_ff: int) -> Dict:
    ks = split_keys(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff)),
        "w_up": dense_init(ks[1], (d_model, d_ff)),
        "w_down": dense_init(ks[2], (d_ff, d_model)),
    }


def dense_mlp_specs(plan: MeshPlan) -> Dict:
    from jax.sharding import PartitionSpec as P

    mx = plan.spec_model_axis
    return {"w_gate": P(None, mx), "w_up": P(None, mx), "w_down": P(mx, None)}


def dense_mlp_forward(p, x):
    """x: (..., d) replicated over model -> P(sum) partial output."""
    g = x @ p["w_gate"].astype(x.dtype)
    u = x @ p["w_up"].astype(x.dtype)
    return swiglu(g, u) @ p["w_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

#: per-call counts of the expert layer, in this order (int32):
#: ``moe_pairs`` (token, expert) pairs routed to held experts, ``moe_rows``
#: expert rows computed (padding included), ``moe_touched`` held experts
#: with at least one pair, ``moe_read`` held experts whose weights are read
MOE_COUNTERS = ("moe_pairs", "moe_rows", "moe_touched", "moe_read")


def init_moe(key, cfg: ModelConfig) -> Dict:
    d, ff = cfg.d_model, cfg.moe_d_ff
    E_held = cfg.held_experts
    ks = split_keys(key, 5)
    p = {
        "router": dense_init(ks[0], (d, cfg.num_experts), scale=0.1),
        "w_gate": dense_init(ks[1], (E_held, d, ff)),
        "w_up": dense_init(ks[2], (E_held, d, ff)),
        "w_down": dense_init(ks[3], (E_held, ff, d)),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_dense_mlp(ks[4], d,
                                     cfg.moe_d_ff * cfg.num_shared_experts)
    return p


def moe_specs(cfg: ModelConfig, plan: MeshPlan) -> Dict:
    from jax.sharding import PartitionSpec as P

    mx = plan.spec_model_axis
    p = {"router": P(),
         "w_gate": P(mx), "w_up": P(mx), "w_down": P(mx)}
    if cfg.num_shared_experts:
        p["shared"] = dense_mlp_specs(plan)
    return p


def route(router, t, cfg: ModelConfig):
    """The router over all ``num_experts``, in float32: softmax scores
    ``(T, E)``, and each token's ``top_k`` experts ``(T, K)`` with their
    gates (renormalised only under ``norm_topk_prob``, then scaled by
    ``routed_scaling_factor``)."""
    logits = jnp.dot(t.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates * cfg.routed_scaling_factor, idx


def _zero_gate_form(p, t, e, g):
    """Every held expert over every token, each weighted by the token's gate
    for it (zero where the token was not routed to it): ``held * T`` rows,
    every held expert's weights read. ``e``/``g``: the (T*K,) local expert
    of each routed pair (``held`` where not held here) and its gate.
    Returns the float32 output (T, d)."""
    T = t.shape[0]
    n_held = p["w_gate"].shape[0]
    tok = jnp.repeat(jnp.arange(T), e.shape[0] // T)
    A = jnp.zeros((T, n_held + 1), jnp.float32).at[tok, e].add(g)
    dt = t.dtype
    h = swiglu(jnp.einsum("td,edf->etf", t, p["w_gate"].astype(dt)),
               jnp.einsum("td,edf->etf", t, p["w_up"].astype(dt)))
    y = jnp.einsum("etf,efd->etd", h, p["w_down"].astype(dt))
    return jnp.einsum("etd,te->td", y.astype(jnp.float32), A[:, :n_held])


def _grouped_form(p, t, e, g):
    """The routed pairs sorted by held expert into one grouped matmul
    (``jax.lax.ragged_dot``): ``T * K`` rows, of which the pairs to experts
    not held here (``e == held``, sorted last) lie in no group and add
    nothing. Same arguments and output as :func:`_zero_gate_form`."""
    T, d = t.shape
    n_held = p["w_gate"].shape[0]
    order = jnp.argsort(e, stable=True)
    tok = order // (e.shape[0] // T)
    sizes = jnp.zeros((n_held,), jnp.int32).at[e].add(1, mode="drop")
    x = t[tok]
    dt = t.dtype
    h = swiglu(jax.lax.ragged_dot(x, p["w_gate"].astype(dt), sizes),
               jax.lax.ragged_dot(x, p["w_up"].astype(dt), sizes))
    y = jax.lax.ragged_dot(h, p["w_down"].astype(dt), sizes)
    # rows in no group are left unwritten: mask them, do not scale them
    y = jnp.where((e[order] < n_held)[:, None], y.astype(jnp.float32), 0.0)
    return jnp.zeros((T, d), jnp.float32).at[tok].add(y * g[order][:, None])


#: the grouped form runs where a call has at least this many tokens and the
#: rows the zero-gate form adds to the grouped form's, ``(held - K) * T``,
#: reach this many: on a TPU v5e the grouped form was 1.4-2.9x as fast at
#: 2048 tokens with 64 held of 6 or 8 a token and 16 of 2 (28672 extra
#: rows), and 1.1-1.4x at 4096-8192 tokens with 16 held of 6; slower at 512
#: tokens and below, and with 16 held of 6 at 1536 (15360) (PERF.md)
GROUPED_MIN_TOKENS = 2048
GROUPED_MIN_EXTRA_ROWS = 24576


def expert_form(n_held: int, tokens: int, top_k: int, platform: str) -> str:
    """The form of one expert-layer call, by its shape and platform:
    ``"grouped"`` (:func:`_grouped_form`) or ``"zero_gate"``
    (:func:`_zero_gate_form`). Off the TPU ``ragged_dot`` has no grouped
    kernel and was at best as fast as the zero-gate form on the CPU, which
    therefore serves there."""
    if (platform == "tpu" and tokens >= GROUPED_MIN_TOKENS
            and (n_held - top_k) * tokens >= GROUPED_MIN_EXTRA_ROWS):
        return "grouped"
    return "zero_gate"


def moe_forward(p, x, cfg: ModelConfig, plan: MeshPlan
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) replicated over the model axis.

    The router scores all ``num_experts``; this device holds
    ``held_experts / tp`` of them (the model axis splits the held range)
    and computes every (token, held expert) pair routed to them, with no
    capacity and no drop. What absent experts would add is left out.

    Returns (partial_out P(sum) over model, aux_load_balance_loss scalar,
    int32 counts :data:`MOE_COUNTERS` of this device).
    """
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    tp = plan.tp
    assert cfg.held_experts % tp == 0, (cfg.held_experts, tp)
    n_held = cfg.held_experts // tp
    T = B * S
    t = x.reshape(T, d)

    with jax.named_scope("moe.route"):
        probs, gates, idx = route(p["router"], t, cfg)
        # load-balance aux loss (Switch-style) over the router's experts:
        # E * sum_e f_e * P_e
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (T, K, E)
        f = onehot.sum(axis=(0, 1)) / (T * K)    # fraction routed per expert
        aux = E * jnp.sum(f * probs.mean(axis=0))
        m_idx = jax.lax.axis_index(plan.model_axis) if tp > 1 else 0
        e = idx.reshape(-1) - (cfg.first_expert + m_idx * n_held)
        held = (e >= 0) & (e < n_held)
        e = jnp.where(held, e, n_held)           # n_held: not held here
        g = jnp.where(held, gates.reshape(-1), 0.0)
        sizes = jnp.zeros((n_held,), jnp.int32).at[e].add(1, mode="drop")

    grouped = expert_form(n_held, T, K, jax.default_backend()) == "grouped"
    with jax.named_scope("moe.experts"):
        out = (_grouped_form if grouped else _zero_gate_form)(p, t, e, g)
    # rows: the grouped form's buffer of T*K pairs, pairs to absent experts
    # included, or every held expert over every token; weights read: the
    # grouped matmul walks only the experts with a pair, the zero-gate form
    # every held expert
    touched = jnp.sum(sizes > 0)
    counts = jnp.stack(
        [jnp.sum(held), jnp.asarray(T * K if grouped else n_held * T),
         touched, touched if grouped else jnp.asarray(n_held)]).astype(
        jnp.int32)

    if cfg.num_shared_experts:                   # both P(sum): defer
        out = out + dense_mlp_forward(p["shared"], t).astype(jnp.float32)
    return out.astype(x.dtype).reshape(B, S, d), aux, counts
