"""ZeRO optimizer-state (and master-param) sharding in SBP (paper §6.4).

The paper's point: ZeRO-DP is ~2K LoC of engineering in PyTorch but falls out
of SBP annotations. Here the *master* fp32 parameters AND the Adam moments
live as ``S(0)``-over-data flat shards of shape ``(DP, TP, chunk)``; each step

1. casts the local shard to the compute dtype (the Fig-14 ``cast`` op) and
   boxes ``S(0) -> B`` over the data axes — an **all-gather of the
   half-precision weights** (Table 2 row S->B, at half the fp32 wire cost);
2. runs fwd/bwd on the gathered weights; the autodiff *transpose* of the
   all-gather is exactly the ``P(sum) -> S(0)`` **reduce-scatter** of
   gradients (Table 2 row P->S) — the compiler inserts it, nobody writes it;
3. updates the local master shard with Adam (fp32).

Replicated-over-model leaves keep one master copy per model shard; their
gradients need a model-axis combine before the update: a sum for leaves with
disjoint per-shard contributions (kv projections, router, ...), a mean for
leaves whose per-shard grads are identical (norm scales). See
``MODEL_SUM_LEAVES``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.models.common import MeshPlan
from repro.optim.adamw import AdamWConfig, adamw_math, adamw_param_update


class ZeroState(NamedTuple):
    step: jnp.ndarray
    mu: Any     # pytree of (DP, TP, chunk) fp32 — same layout as the masters
    nu: Any


# Model-replicated params whose per-device gradient contributions are
# DISJOINT (each model shard computes grads only through its kv-head /
# B,C-group / expert slice): combine = psum. All other replicated leaves have
# IDENTICAL per-shard grads: combine = pmean.
MODEL_SUM_LEAVES = frozenset(
    {"wk", "wv", "bk", "bv", "q_norm", "k_norm", "w_bc", "conv_bc", "router"})


def _chunk_size(local_size: int, dp: int) -> int:
    return math.ceil(local_size / dp)


def _leaf_name(path) -> str:
    for p in reversed(path):
        k = getattr(p, "key", None)
        if k is not None:
            return k
    return ""


def _spec_axes(spec):
    flat = []
    for entry in spec:
        names = entry if isinstance(entry, tuple) else (entry,)
        flat.extend(n for n in names if n is not None)
    return flat


def local_shape_of(global_shape, spec, plan: MeshPlan):
    shape = list(global_shape)
    for dim, entry in enumerate(spec):
        names = entry if isinstance(entry, tuple) else (entry,)
        for n in names:
            if n is not None:
                shape[dim] //= plan.axis_size(n)
    return tuple(shape)


# ---------------------------------------------------------------------------
# flat-shard layout
# ---------------------------------------------------------------------------

def master_specs(params_specs, plan: MeshPlan):
    """PartitionSpecs of the flat (DP, TP, chunk) master/moment leaves."""
    from jax.sharding import PartitionSpec as P

    dp_axes = plan.data_axes
    mx = plan.model_axis if plan.model_axis in plan.axis_names else None
    leaf = P(dp_axes if len(dp_axes) > 1 else dp_axes[0], mx, None)
    return jax.tree.map(lambda _: leaf, params_specs,
                        is_leaf=lambda s: isinstance(s, P))


def zero_state_specs(params_specs, plan: MeshPlan):
    m = master_specs(params_specs, plan)
    from jax.sharding import PartitionSpec as P

    return ZeroState(P(), m, jax.tree.map(lambda s: s, m))


def master_shapes(params_global, specs, plan: MeshPlan):
    """Global ShapeDtypeStructs of the flat master leaves."""
    def leaf(p, spec):
        n_loc = math.prod(local_shape_of(p.shape, spec, plan)) if p.shape else 1
        return jax.ShapeDtypeStruct(
            (plan.dp, plan.tp, _chunk_size(n_loc, plan.dp)), jnp.float32)

    from jax.sharding import PartitionSpec as P

    return jax.tree.map(leaf, params_global, specs,
                        is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct))


def zero_state_shapes(params_global, specs, plan: MeshPlan):
    m = master_shapes(params_global, specs, plan)
    return ZeroState(jax.ShapeDtypeStruct((), jnp.int32), m,
                     jax.tree.map(lambda x: x, m))


def shard_master_local(p_local, plan: MeshPlan):
    """(inside shard_map) full local param -> (1, 1, chunk) master shard."""
    dp = plan.dp
    flat = p_local.reshape(-1).astype(jnp.float32)
    chunk = _chunk_size(flat.size, dp)
    flat = jnp.pad(flat, (0, dp * chunk - flat.size))
    if dp > 1:
        axes = plan.data_axes if len(plan.data_axes) > 1 else plan.data_axes[0]
        idx = jax.lax.axis_index(axes)
        sh = jax.lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)
    else:
        sh = flat
    return sh.reshape(1, 1, chunk)


def gather_master_local(m_local, local_shape, compute_dtype, plan: MeshPlan):
    """(inside shard_map) (1,1,chunk) master shard -> full local param.

    Implements Fig 14: fp32 master -> cast -> S(0)->B all-gather in the
    compute dtype (half the wire bytes of gathering fp32).
    """
    sh = m_local.reshape(-1).astype(compute_dtype)     # the Fig-14 cast op
    if plan.dp > 1:
        axes = plan.data_axes if len(plan.data_axes) > 1 else plan.data_axes[0]
        flat = jax.lax.all_gather(sh, axes, tiled=True)
    else:
        flat = sh
    n = math.prod(local_shape) if local_shape else 1
    return flat[:n].reshape(local_shape)


def init_zero_state_local(masters_local, plan: MeshPlan) -> ZeroState:
    mu = jax.tree.map(lambda m: jnp.zeros_like(m, jnp.float32), masters_local)
    return ZeroState(jnp.zeros((), jnp.int32), mu, jax.tree.map(jnp.copy, mu))


# ---------------------------------------------------------------------------
# global flat-shard kernels (no shard_map) — the per-stage entry points the
# pipelined opt actors and the monolithic train engine share. Same layout as
# the shard_map kernels above, but over the *global* array: the whole
# (dp, 1, chunk) flat master lives in one jax.Array (optionally committed to
# a NamedSharding over the leading dp axis, in which case XLA inserts the
# S(0)->B all-gather / its reduce-scatter transpose for free).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("dp",))
def shard_flat(x, *, dp: int):
    """Full tensor -> flat ``(dp, 1, chunk)`` fp32 shards, zero-padded.

    The global-view dual of :func:`shard_master_local`. Padding stays exactly
    zero through AdamW updates (0 moments, 0 grad, 0 weight-decay term), so
    gather -> re-shard across different dp values is bitwise lossless.
    """
    flat = x.reshape(-1).astype(jnp.float32)
    chunk = _chunk_size(flat.size, dp)
    flat = jnp.pad(flat, (0, dp * chunk - flat.size))
    return flat.reshape(dp, 1, chunk)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def gather_flat(m, *, shape, dtype):
    """Flat ``(dp, 1, chunk)`` shards -> full tensor in ``dtype``.

    The cast happens *before* the reshape — Fig 14's ``cast`` op ahead of the
    S(0)->B gather, so a sharded master crosses the wire at compute-dtype
    width, not fp32.
    """
    flat = m.astype(jnp.dtype(dtype)).reshape(-1)
    n = math.prod(shape) if shape else 1
    return flat[:n].reshape(shape)


def init_zero_flat(masters) -> ZeroState:
    """Zero moments in the masters' flat (dp, 1, chunk) layout."""
    mu = jax.tree.map(lambda m: jnp.zeros_like(m, jnp.float32), masters)
    return ZeroState(jnp.zeros((), jnp.int32), mu, jax.tree.map(jnp.copy, mu))


def zero_stage_update(masters: Dict[str, Any], grads: Dict[str, Any],
                      state: ZeroState, lr, *, dp: int, beta1: float,
                      beta2: float, eps: float, weight_decay: float):
    """One optimizer stage's ZeRO AdamW step on flat masters.

    ``masters``: ``{name: (dp, 1, chunk) fp32}``; ``grads``: ``{name:
    full-shape pre-clipped fp32}``. Per-element math is
    :func:`adamw_param_update` (via the shared ``adamw_math`` body), which is
    elementwise and therefore layout-invariant — the flat update is bitwise
    the dense update reshaped. Returns ``(new_masters, new ZeroState)``.
    """
    new_step = state.step + 1
    new_m: Dict[str, Any] = {}
    new_mu: Dict[str, Any] = {}
    new_nu: Dict[str, Any] = {}
    for n, m in masters.items():
        gf = shard_flat(grads[n], dp=dp)
        new_m[n], new_mu[n], new_nu[n] = adamw_param_update(
            m, gf, state.mu[n], state.nu[n], new_step, lr,
            beta1=beta1, beta2=beta2, eps=eps, weight_decay=weight_decay)
    return new_m, ZeroState(new_step, new_mu, new_nu)


# ---------------------------------------------------------------------------
# gradient combine over the model axis for replicated leaves
# ---------------------------------------------------------------------------

def model_combine_tree(params_specs, plan: MeshPlan):
    """Per-leaf model-axis gradient combine: 'none' | 'sum'.

    With gathered (varying) masters, EVERY model-replicated leaf's per-shard
    gradient contributions are disjoint partial sums (each shard's autodiff
    covers only its own branch of every psum-mediated path), so the combine
    is always a psum. Redundant non-psum-mediated loss terms (the MoE aux
    loss) are pmean-mediated in the model so this stays exact.
    """
    from jax.sharding import PartitionSpec as P
    import jax.tree_util as jtu

    def mode(path, spec):
        return "none" if plan.model_axis in _spec_axes(spec) else "sum"

    return jtu.tree_map_with_path(mode, params_specs,
                                  is_leaf=lambda s: isinstance(s, P))


def combine_model_grads(grads, combine, plan: MeshPlan):
    if plan.tp == 1:
        return grads

    def fix(g, mode):
        if mode == "sum":
            return jax.lax.psum(g, plan.model_axis)
        if mode == "mean":
            return jax.lax.pmean(g, plan.model_axis)
        return g

    return jax.tree.map(fix, grads, combine)


# ---------------------------------------------------------------------------
# the update (operates on flat shards)
# ---------------------------------------------------------------------------

def _sumsq(g):
    """fp32 sum of squares over the flat element order. ZeRO and plain DP
    both reduce this 1-d layout, so their clip norms agree bitwise."""
    return jnp.sum(jnp.square(g.astype(jnp.float32).reshape(-1)))


def zero_adamw_update(cfg: AdamWConfig, masters, grads_flat, state: ZeroState,
                      plan: MeshPlan, replication, lr_scale=1.0):
    """Adam on (1,1,chunk) master shards. ``grads_flat`` has the same layout
    (already reduce-scattered over data and model-combined)."""
    dp = plan.dp
    tp = plan.tp
    axes = plan.data_axes if len(plan.data_axes) > 1 else plan.data_axes[0]

    sumsq = sum(
        _sumsq(g) / r
        for g, r in zip(jax.tree.leaves(grads_flat),
                        jax.tree.leaves(replication)))
    if dp > 1:
        sumsq = jax.lax.psum(sumsq, axes)
    if tp > 1:
        sumsq = jax.lax.psum(sumsq, plan.model_axis)
    norm = jnp.sqrt(sumsq)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(norm, 1e-12)) \
        if cfg.grad_clip else jnp.float32(1.0)

    step = state.step + 1
    lr = cfg.lr * lr_scale

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        return adamw_math(p, g, m, v, step, lr, cfg.beta1, cfg.beta2,
                          cfg.eps, cfg.weight_decay)

    out = jax.tree.map(upd, masters, grads_flat, state.mu, state.nu)
    is3 = lambda t: isinstance(t, tuple) and len(t) == 3
    new_m = jax.tree.map(lambda t: t[0], out, is_leaf=is3)
    new_mu = jax.tree.map(lambda t: t[1], out, is_leaf=is3)
    new_nu = jax.tree.map(lambda t: t[2], out, is_leaf=is3)
    return new_m, ZeroState(step, new_mu, new_nu), norm


# ---------------------------------------------------------------------------
# plain (non-ZeRO) data parallelism — the §6.2 baseline
# ---------------------------------------------------------------------------

def plain_dp_adamw_update(cfg: AdamWConfig, params, grads, state,
                          plan: MeshPlan, replication, lr_scale=1.0):
    """P(sum) -> B all-reduce of grads, replicated optimizer states."""
    from repro.optim.adamw import AdamWState

    dp = plan.dp
    axes = plan.data_axes if len(plan.data_axes) > 1 else plan.data_axes[0]

    def reduce_grad(g):
        g = g.astype(jnp.float32) / dp
        return jax.lax.psum(g, axes) if dp > 1 else g

    grads = jax.tree.map(reduce_grad, grads)
    sumsq = sum(
        _sumsq(g) / r
        for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(replication)))
    if plan.tp > 1:
        sumsq = jax.lax.psum(sumsq, plan.model_axis)
    norm = jnp.sqrt(sumsq)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(norm, 1e-12)) \
        if cfg.grad_clip else jnp.float32(1.0)

    step = state.step + 1
    lr = cfg.lr * lr_scale

    def upd(p, g, m, v):
        new_p, m, v = adamw_math(p.astype(jnp.float32), g * scale, m, v,
                                 step, lr, cfg.beta1, cfg.beta2, cfg.eps,
                                 cfg.weight_decay)
        return new_p.astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, state.mu, state.nu)
    is3 = lambda t: isinstance(t, tuple) and len(t) == 3
    new_params = jax.tree.map(lambda t: t[0], out, is_leaf=is3)
    new_mu = jax.tree.map(lambda t: t[1], out, is_leaf=is3)
    new_nu = jax.tree.map(lambda t: t[2], out, is_leaf=is3)
    new_params = _certify_replicated(new_params, replication, plan)
    new_mu = _certify_replicated(new_mu, replication, plan)
    new_nu = _certify_replicated(new_nu, replication, plan)
    return new_params, AdamWState(step, new_mu, new_nu), norm


def _certify_replicated(tree, replication, plan: MeshPlan):
    """pmean leaves that are logically replicated over the model axis.

    Mathematically a no-op (values equal by construction); certifies
    replication to shard_map's vma checker, whose inference is conservative
    through remat/custom_vjp regions. Applies even when the model axis has
    size 1 (vma still tracks it).
    """
    if plan.model_axis not in plan.axis_names:
        return tree

    def fix(x, r):
        vma = jax.typeof(x).vma
        if plan.model_axis not in vma:
            return x
        if r <= 1 and plan.tp > 1:
            return x      # genuinely model-sharded leaf: varying is correct
        return jax.lax.pmean(x, plan.model_axis)

    return jax.tree.map(fix, tree, replication)
