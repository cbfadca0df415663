"""Shared model-building blocks + the SBP-annotated collective helper.

All model code runs *inside* ``shard_map`` over the production mesh; every
collective is written as an explicit SBP transition via :class:`Boxer`, so the
model source reads as OneFlow-style SBP annotations (the compiler-inserted
boxing ops of paper §3.2 appear literally in the code).
"""
from __future__ import annotations

import dataclasses
import math
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.boxing import boxing_fn
from repro.core.sbp import Split, ndsbp


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """How the mesh axes are used by the model code."""

    axis_names: Tuple[str, ...]          # e.g. ("pod", "data", "model")
    axis_sizes: Tuple[int, ...]
    model_axis: str = "model"

    @property
    def data_axes(self) -> Tuple[str, ...]:
        return tuple(n for n in self.axis_names if n != self.model_axis)

    @property
    def tp(self) -> int:
        if self.model_axis not in self.axis_names:
            return 1          # FSDP plan: every mesh axis is a data axis
        return self.axis_sizes[self.axis_names.index(self.model_axis)]

    @property
    def dp(self) -> int:
        return math.prod(s for n, s in zip(self.axis_names, self.axis_sizes)
                         if n != self.model_axis)

    def axis_size(self, name: str) -> int:
        return self.axis_sizes[self.axis_names.index(name)]

    @property
    def spec_model_axis(self):
        """model axis name for PartitionSpecs; None under the FSDP plan."""
        return self.model_axis if self.model_axis in self.axis_names else None

    @staticmethod
    def single_device() -> "MeshPlan":
        return MeshPlan(("data", "model"), (1, 1))


class Boxer:
    """SBP-transition helper bound to a mesh plan, usable inside shard_map.

    ``bx(x, "S(0),B,P", "S(0),B,B")`` emits exactly the collective the boxing
    cost model prices for that transition. The logical shape is derived from
    the local shard shape and the source signature.
    """

    def __init__(self, plan: MeshPlan):
        self.plan = plan

    def __call__(self, x, src, dst):
        src_n, dst_n = ndsbp(src), ndsbp(dst)
        logical = list(x.shape)
        for comp, size in zip(src_n, self.plan.axis_sizes):
            if isinstance(comp, Split):
                logical[comp.axis] *= size
        fn = boxing_fn(src_n, dst_n, self.plan.axis_names,
                       self.plan.axis_sizes, tuple(logical))
        return fn(x)

    # frequent shortcuts ------------------------------------------------------
    def psum_model(self, x):
        return jax.lax.psum(x, self.plan.model_axis)

    def psum_data(self, x):
        for ax in self.plan.data_axes:
            x = jax.lax.psum(x, ax)
        return x

    def pmean_data(self, x):
        return self.psum_data(x) / self.plan.dp

    def allgather_model(self, x, axis: int):
        return jax.lax.all_gather(x, self.plan.model_axis, axis=axis, tiled=True)


# ---------------------------------------------------------------------------
# Megatron's "f" operator: identity forward, psum backward.
#
# A replicated activation consumed by model-parallel branches (each device's
# branch sees only its head/expert/vocab slice) has DISJOINT per-device
# gradient contributions; the true dL/dx is their sum. Forward needs nothing
# (x is replicated); backward needs a psum. This is the conjugate of the
# forward psum ("g") whose backward is the identity.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def grad_sync(x, axis_name: str):
    return x


def _grad_sync_fwd(x, axis_name):
    return x, None


def _grad_sync_bwd(axis_name, _, g):
    return (jax.lax.psum(g, axis_name),)


grad_sync.defvjp(_grad_sync_fwd, _grad_sync_bwd)


def maybe_grad_sync(x, plan: "MeshPlan"):
    return grad_sync(x, plan.model_axis) if plan.tp > 1 else x


def bound_axes(axis_names):
    """Which of ``axis_names`` are live shard_map axes in this trace."""
    live = set(jax.sharding.get_abstract_mesh().manual_axes)
    return tuple(n for n in axis_names if n in live)


def force_vary(x, axis_names):
    """Make x's vma cover all live ``axis_names`` (scan carries must have
    a consistent vma across architectures; the pcast is free). No-op outside
    shard_map."""
    names = bound_axes(axis_names)
    if not names:
        return x
    vma = jax.typeof(x).vma
    missing = tuple(n for n in names if n not in vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def certified_pmean(x, axis_name):
    """pmean that no-ops when ``axis_name`` is not a live shard_map axis
    (e.g. smoke tests calling model code outside shard_map)."""
    if not bound_axes((axis_name,)):
        return x
    return jax.lax.pmean(force_vary(x, (axis_name,)), axis_name)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * w


def rope_freqs(head_dim: int, rope_fraction: float, theta: float):
    rot = int(head_dim * rope_fraction) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return rot, inv


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention magnitude factor (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_pos: int, beta_fast: float, beta_slow: float):
    """YaRN rope frequencies over ``dim`` channels (DeepSeek-V2): the
    plain ``1 / theta**(2i/dim)`` below the correction range (dimensions
    that turn more than ``beta_fast`` times over the original context), the
    interpolated ``1 / (factor * theta**(2i/dim))`` above it (fewer than
    ``beta_slow`` turns), and a linear blend between."""
    def turns_dim(turns):
        return (dim * math.log(original_max_pos / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x, positions, rope_fraction: float = 1.0, theta: float = 1e4,
               inv_freq=None, scale: float = 1.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq). ``inv_freq``
    (head_dim/2 frequencies, e.g. :func:`yarn_inv_freq`) replaces the plain
    ones over the whole head; ``scale`` multiplies cos and sin."""
    hd = x.shape[-1]
    if inv_freq is None:
        rot, inv = rope_freqs(hd, rope_fraction, theta)
    else:
        rot, inv = hd, inv_freq
    if rot == 0:
        return x
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., seq, rot/2)
    cos = jnp.cos(ang)[..., None, :] * scale
    sin = jnp.sin(ang)[..., None, :] * scale
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x_rot.shape).astype(x.dtype)
    return jnp.concatenate([out, x_pass], axis=-1) if rot < hd else out


def swiglu(gate, up):
    return jax.nn.silu(gate) * up


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32, scale=1.0):
    fan_in = shape[in_axis]
    std = scale / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))
