"""Lowering — turn (LogicalGraph, Plan) into an executable SPMD program.

This is the compiler's final stage (paper Fig 1/5): every op runs *locally* on
its shard under ``shard_map``; wherever producer SBP != consumer SBP, the
planner's boxing edge becomes an explicit ``jax.lax`` collective
(:func:`repro.core.boxing.boxing_fn`). Partial-value tensors flow through as
real unreduced per-device arrays, so deferred reduction (§3.3) happens exactly
as planned.

Two entry points share one subgraph lowerer:

* :func:`lower_plan` — the whole graph as one jitted ``shard_map`` program
  (:class:`PhysicalProgram`).
* :func:`lower_stages` — the graph cut by a
  :class:`repro.core.graph.StagePartition` into per-stage jitted programs
  (:class:`StagedProgram`), with boxing at stage boundaries. This is the
  compiler half of actor-driven pipeline execution (§4.3): the runtime half
  lives in :mod:`repro.runtime.pipeline`.

These (and the training variants :func:`lower_train_plan` /
:func:`lower_train_stages`) are compiler internals; user code reaches them
through the :mod:`repro.api` frontend — ``api.compile(graph, ...)`` picks
the plan/partition/quotas and wraps the result in a uniform ``Session``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.boxing import boxing_fn
from repro.core.graph import LogicalGraph, LOp, LTensor, StagePartition
from repro.core.planner import Plan
from repro.core.sbp import Broadcast, NdSbp, Split

from repro.compat import shard_map


def _split_axes_for(sig: NdSbp, tensor_axis: int, axis_names: Sequence[str]) -> List[str]:
    """Mesh axis names on which ``tensor_axis`` is split under ``sig``."""
    return [name for comp, name in zip(sig, axis_names)
            if isinstance(comp, Split) and comp.axis == tensor_axis]


def _partial_axes(sig: NdSbp, axis_names: Sequence[str]) -> List[str]:
    return [name for comp, name in zip(sig, axis_names) if comp.is_partial]


_UNARY_FNS = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "neg": jnp.negative,
    "identity": lambda x: x,
    "scale2": lambda x: 2.0 * x,
}


def _local_op(op: LOp, in_sigs: Tuple[NdSbp, ...], out_sig: NdSbp,
              axis_names: Sequence[str], mesh_shape: Sequence[int]):
    """Return fn(local_inputs) -> local_output implementing op under the sigs."""
    kind = op.spec.name
    attrs = op.spec.attrs

    if kind == "matmul":
        def f(x, w):
            return jnp.dot(x, w)
        return f

    if kind == "ew_binary":
        opn = attrs.get("op", "add")
        fn = {"add": jnp.add, "mul": jnp.multiply}[opn]
        return fn

    if kind == "ew_unary":
        return _UNARY_FNS[attrs.get("fn", "identity")]

    if kind == "bias_add":
        return lambda x, b: x + b[None, :]

    if kind == "reduce":
        axis, red = attrs["axis"], attrs.get("op", "sum")
        jfn = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[red]
        return lambda x: jfn(x, axis=axis, keepdims=True)

    if kind == "softmax":
        # hierarchical softmax (paper Fig 11b): local max/sum + global combine
        red_axes = _split_axes_for(in_sigs[0], 1, axis_names)

        def f(x):
            m = jnp.max(x, axis=1, keepdims=True)
            for ax in red_axes:
                m = jax.lax.pmax(m, ax)
            e = jnp.exp(x - m)
            s = jnp.sum(e, axis=1, keepdims=True)
            for ax in red_axes:
                s = jax.lax.psum(s, ax)
            return e / s
        return f

    if kind == "softmax_xent":
        red_axes = _split_axes_for(in_sigs[0], 1, axis_names)
        vocab_frac = 1
        for name, size in zip(axis_names, mesh_shape):
            if name in red_axes:
                vocab_frac *= size
        local_c = op.inputs[0].shape[1] // vocab_frac

        def f(logits, labels):
            m = jnp.max(logits, axis=1, keepdims=True)
            for ax in red_axes:
                m = jax.lax.pmax(m, ax)
            e = jnp.exp(logits - m)
            s = jnp.sum(e, axis=1, keepdims=True)
            for ax in red_axes:
                s = jax.lax.psum(s, ax)
            # local gather of the label logit (zero when out of shard range)
            if red_axes:
                offset = jnp.zeros((), jnp.int32)
                stride = 1
                for name, size in reversed(list(zip(axis_names, mesh_shape))):
                    if name in red_axes:
                        offset = offset + jax.lax.axis_index(name) * stride * local_c
                        stride *= size
                local_ids = labels - offset
                in_range = (local_ids >= 0) & (local_ids < local_c)
                safe = jnp.clip(local_ids, 0, local_c - 1)
                picked = jnp.take_along_axis(logits, safe[:, None], axis=1)
                z = jnp.where(in_range[:, None], picked - m, 0.0)
                # output is P(sum) over red_axes: exactly one shard contributes
                return jnp.log(s) - z
            z = jnp.take_along_axis(logits, labels[:, None], axis=1)
            return jnp.log(s) - (z - m)
        return f

    if kind == "embedding":
        red_axes = _split_axes_for(in_sigs[0], 0, axis_names)  # vocab split
        hid_split = _split_axes_for(in_sigs[0], 1, axis_names)

        def f(table, ids):
            if red_axes:
                local_v = table.shape[0]
                offset = jnp.zeros((), jnp.int32)
                stride = 1
                for name, size in reversed(list(zip(axis_names, mesh_shape))):
                    if name in red_axes:
                        offset = offset + jax.lax.axis_index(name) * stride * local_v
                        stride *= size
                local_ids = ids - offset
                in_range = (local_ids >= 0) & (local_ids < local_v)
                safe = jnp.clip(local_ids, 0, local_v - 1)
                out = table[safe]
                return jnp.where(in_range[:, None], out, 0.0)  # P(sum)
            return table[ids]
        return f

    raise NotImplementedError(f"no local lowering for op kind {kind}")


def _materialized(sig: NdSbp) -> NdSbp:
    """Partial-free storage signature: P components become B (all-reduce).

    Tensors that cross a jit boundary (graph outputs, pipeline-stage
    boundaries) must be real globally-addressable arrays — partial-value only
    exists *inside* a shard_map program.
    """
    return NdSbp(tuple(Broadcast() if c.is_partial else c for c in sig))


def _lower_subgraph(graph: LogicalGraph, plan: Plan, mesh,
                    ops: Sequence[LOp],
                    in_tensors: Sequence[LTensor],
                    out_tensors: Sequence[LTensor],
                    in_sbp: Dict[str, NdSbp],
                    out_sbp: Dict[str, NdSbp]):
    """shard_map program running ``ops`` from ``in_tensors`` to ``out_tensors``.

    ``in_sbp``/``out_sbp`` give the *stored* (partial-free) signatures at the
    subgraph boundary; inside, tensors follow the plan exactly, including
    partial-value storage.
    """
    axis_names = tuple(mesh.axis_names)
    mesh_shape = tuple(mesh.devices.shape)

    for t in in_tensors:
        if in_sbp[t.name].has_partial:
            raise ValueError(f"boundary input {t.name} stored as partial-value")
    for t in out_tensors:
        if out_sbp[t.name].has_partial:
            raise ValueError(f"boundary output {t.name} stored as partial-value")

    in_specs = tuple(graph.placement.partition_spec(in_sbp[t.name])
                     for t in in_tensors)
    out_specs = tuple(graph.placement.partition_spec(out_sbp[t.name])
                      for t in out_tensors)

    def local_program(*local_inputs):
        env = {t.name: v for t, v in zip(in_tensors, local_inputs)}
        cur_sbp = {t.name: in_sbp[t.name] for t in in_tensors}
        for op in ops:
            in_sigs = plan.op_in_sbp[op.name]
            raw_sig = plan.op_out_sbp[op.name]
            stored_sig = plan.tensor_sbp[op.output.name]
            args = []
            for t, want in zip(op.inputs, in_sigs):
                have = cur_sbp[t.name]
                v = env[t.name]
                if have != want:
                    v = boxing_fn(have, want, axis_names, mesh_shape, t.shape)(v)
                args.append(v)
            fn = _local_op(op, in_sigs, raw_sig, axis_names, mesh_shape)
            val = fn(*args)
            if raw_sig != stored_sig:  # epilogue boxing (e.g. P materialization)
                val = boxing_fn(raw_sig, stored_sig, axis_names, mesh_shape,
                                op.output.shape)(val)
            env[op.output.name] = val
            cur_sbp[op.output.name] = stored_sig
        outs = []
        for t in out_tensors:
            v, have, want = env[t.name], cur_sbp[t.name], out_sbp[t.name]
            if have != want:  # boundary boxing (e.g. P -> B materialization)
                v = boxing_fn(have, want, axis_names, mesh_shape, t.shape)(v)
            outs.append(v)
        return tuple(outs)

    return shard_map(local_program, mesh=mesh,
                     in_specs=in_specs, out_specs=out_specs)


def lower_plan(graph: LogicalGraph, plan: Plan, mesh) -> "PhysicalProgram":
    for t in graph.inputs:
        if plan.tensor_sbp[t.name].has_partial:
            raise ValueError(f"graph input {t.name} planned as partial-value")
    sinks = graph.sinks()
    for t in sinks:
        if plan.tensor_sbp[t.name].has_partial:
            raise ValueError(f"graph output {t.name} planned as partial-value; "
                             "planner should have boxed it")
    boundary = {t.name: plan.tensor_sbp[t.name] for t in list(graph.inputs) + sinks}
    mapped = _lower_subgraph(graph, plan, mesh, graph.topo_ops(),
                             graph.inputs, sinks, boundary, boundary)
    return PhysicalProgram(graph, plan, mesh, mapped, sinks)


class PhysicalProgram:
    """Executable physical graph: shard_map program + metadata.

    Calling it always returns a tuple of sink values, in ``self.sinks``
    order — including for single-sink graphs.
    """

    def __init__(self, graph, plan, mesh, fn, sinks):
        self.graph, self.plan, self.mesh = graph, plan, mesh
        self._fn = jax.jit(fn)
        self.sinks = sinks

    def __call__(self, *global_inputs) -> Tuple:
        return tuple(self._fn(*global_inputs))

    def lower(self, *global_inputs):
        return self._fn.lower(*global_inputs)


# ---------------------------------------------------------------------------
# Stage-partitioned lowering (paper §4.3): each pipeline stage becomes its own
# jitted program; tensors crossing a stage boundary are stored partial-free.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StageProgram:
    """One lowered pipeline stage: a jitted callable plus its interface.

    ``fn(*values)`` takes one value per ``input_names`` entry (graph inputs
    and/or boundary tensors from earlier stages) and returns a tuple with one
    value per ``output_names`` entry (boundary tensors and/or graph sinks).
    """

    index: int
    fn: Callable
    input_names: Tuple[str, ...]
    output_names: Tuple[str, ...]
    mesh: object = None
    in_shardings: Optional[Tuple] = None    # set when stages own distinct meshes

    def place_inputs(self, values: Sequence) -> List:
        """Transfer boundary values onto this stage's devices (the explicit
        cross-stage send; a no-op when all stages share one mesh)."""
        if self.in_shardings is None:
            return list(values)
        return [jax.device_put(v, sh)
                for v, sh in zip(values, self.in_shardings)]


class StagedProgram:
    """A pipeline of independently-jitted stage programs.

    Sequential execution (``__call__``) is the reference semantics; the actor
    runtime adapter (:mod:`repro.runtime.pipeline`) drives the same stage
    callables concurrently, one actor per stage, with register quotas bounding
    in-flight microbatches.
    """

    def __init__(self, graph: LogicalGraph, plan: Plan,
                 partition: StagePartition, stages: List[StageProgram],
                 sinks: List[LTensor], boundary_sbp: Dict[str, NdSbp]):
        self.graph, self.plan, self.partition = graph, plan, partition
        self.stages = stages
        self.sinks = sinks
        self.boundary_sbp = boundary_sbp

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    @property
    def input_names(self) -> List[str]:
        return [t.name for t in self.graph.inputs]

    def __call__(self, *global_inputs) -> Tuple:
        if len(global_inputs) != len(self.graph.inputs):
            raise ValueError(f"expected {len(self.graph.inputs)} inputs, "
                             f"got {len(global_inputs)}")
        env = {t.name: v for t, v in zip(self.graph.inputs, global_inputs)}
        for stage in self.stages:
            args = stage.place_inputs([env[n] for n in stage.input_names])
            outs = stage.fn(*args)
            env.update(zip(stage.output_names, outs))
        return tuple(env[t.name] for t in self.sinks)


@dataclasses.dataclass
class _StageInterface:
    """Boundary interface of one pipeline stage: which tensors enter and
    leave it, with their stored (partial-free) signatures."""

    ops: List[LOp]
    in_tensors: List[LTensor]
    out_tensors: List[LTensor]
    in_sbp: Dict[str, NdSbp]
    out_sbp: Dict[str, NdSbp]


def _stage_interfaces(graph: LogicalGraph, plan: Plan,
                      partition: StagePartition):
    """Compute every stage's boundary: ``(sinks, boundary_sbp, interfaces)``.

    Shared by forward-only (:func:`lower_stages`) and training
    (:func:`lower_train_stages`) lowering.

    ``boundary_sbp`` maps every stage-crossing (or sink) tensor to its
    *materialized* signature (``_materialized`` rewrites P components to B),
    which is the invariant the static verifier leans on:
    :func:`repro.analysis.sbp_check.check_sbp` treats these signatures as
    the stage-boundary ground truth (no partial value crosses a stage), and
    :mod:`repro.analysis.membound` prices register payloads from them.
    """
    sinks = graph.sinks()
    sink_names = {t.name for t in sinks}
    producer_stage = {t.name: partition.stage_of[t.producer.name]
                      for t in graph.tensors if t.producer is not None}

    # tensors leaving each stage: consumed by a later stage, or graph sinks
    stage_out: Dict[int, List[LTensor]] = {s: [] for s in range(partition.num_stages)}
    boundary_sbp: Dict[str, NdSbp] = {}
    for op in graph.topo_ops():
        t = op.output
        ps = producer_stage[t.name]
        consumer_stages = {partition.stage_of[c.name] for c in graph.consumers(t)}
        crosses = any(cs > ps for cs in consumer_stages)
        if crosses or t.name in sink_names:
            stage_out[ps].append(t)
            boundary_sbp[t.name] = _materialized(plan.tensor_sbp[t.name])

    for t in graph.inputs:
        if plan.tensor_sbp[t.name].has_partial:
            raise ValueError(f"graph input {t.name} planned as partial-value")

    interfaces: List[_StageInterface] = []
    for s in range(partition.num_stages):
        ops = partition.ops_in(graph, s)
        in_here = {t.name for op in ops for t in op.inputs}
        produced_here = {op.output.name for op in ops}
        # stage inputs in deterministic order: graph inputs first, then
        # boundary tensors in production (topo) order
        in_tensors: List[LTensor] = [
            t for t in graph.inputs if t.name in in_here]
        in_tensors += [
            t for sp in range(s) for t in stage_out[sp]
            if t.name in in_here and t.name not in produced_here]
        in_sbp = {}
        for t in in_tensors:
            in_sbp[t.name] = (plan.tensor_sbp[t.name] if t.producer is None
                              else boundary_sbp[t.name])
        out_tensors = stage_out[s]
        out_sbp = {t.name: boundary_sbp[t.name] for t in out_tensors}
        interfaces.append(_StageInterface(ops, in_tensors, out_tensors,
                                          in_sbp, out_sbp))
    return sinks, boundary_sbp, interfaces


def _boundary_shardings(placement, mesh, tensors: Sequence[LTensor],
                        sbp: Dict[str, NdSbp]) -> Tuple:
    """NamedShardings for boundary tensors on one stage's mesh — used for
    the explicit cross-stage transfers when stages own distinct meshes."""
    return tuple(
        jax.sharding.NamedSharding(mesh, placement.partition_spec(sbp[t.name]))
        for t in tensors)


def _resolve_meshes(partition: StagePartition, mesh,
                    stage_meshes: Optional[Sequence]):
    if stage_meshes is not None:
        if len(stage_meshes) != partition.num_stages:
            raise ValueError(f"need {partition.num_stages} stage meshes, "
                             f"got {len(stage_meshes)}")
        return list(stage_meshes)
    if mesh is None:
        raise ValueError("pass either mesh or stage_meshes")
    return [mesh] * partition.num_stages


def lower_stages(graph: LogicalGraph, plan: Plan, partition: StagePartition,
                 mesh=None, stage_meshes: Optional[Sequence] = None
                 ) -> StagedProgram:
    """Lower each pipeline stage of ``partition`` independently.

    ``mesh`` lowers every stage onto the same device mesh (stages share
    devices; pipelining overlaps host work and microbatches). Alternatively
    ``stage_meshes`` gives one mesh per stage — same axis names/sizes but
    possibly *disjoint* devices, the paper's placement of one stage per device
    group. Tensors crossing a stage boundary are stored with their
    :func:`_materialized` (partial-free) signature and boxed on exit.
    """
    meshes = _resolve_meshes(partition, mesh, stage_meshes)
    sinks, boundary_sbp, interfaces = _stage_interfaces(graph, plan, partition)

    stages: List[StageProgram] = []
    for s, iface in enumerate(interfaces):
        mapped = _lower_subgraph(graph, plan, meshes[s], iface.ops,
                                 iface.in_tensors, iface.out_tensors,
                                 iface.in_sbp, iface.out_sbp)
        in_shardings = None
        if stage_meshes is not None:
            in_shardings = _boundary_shardings(
                graph.placement, meshes[s], iface.in_tensors, iface.in_sbp)
        stages.append(StageProgram(
            index=s, fn=jax.jit(mapped),
            input_names=tuple(t.name for t in iface.in_tensors),
            output_names=tuple(t.name for t in iface.out_tensors),
            mesh=meshes[s], in_shardings=in_shardings))
    return StagedProgram(graph, plan, partition, stages, sinks, boundary_sbp)


# ---------------------------------------------------------------------------
# Training lowering (paper §4.3 + the JaxPP-style MPMD fwd/bwd decomposition):
# each forward stage is differentiated with jax.vjp so residuals/activations
# stay stage-local (they live inside the returned vjp closure, a pytree the
# runtime stashes in the forward actor's out register) while cotangents flow
# backward across stage boundaries. The optimizer update is its own tiny
# program per stage. The runtime half lives in repro.runtime.pipeline.
# ---------------------------------------------------------------------------

@jax.jit
def sgd_update(w, g, lr):
    """The per-stage optimizer-update program: plain SGD.

    One shared jitted callable so the pipelined step and the monolithic
    reference (:func:`lower_train_plan`) apply a *bit-identical* update.
    fp32 math, result cast back to the param dtype (bf16 params train with
    fp32-accumulated gradients).
    """
    return (w.astype(jnp.float32) - lr * g.astype(jnp.float32)).astype(w.dtype)


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Mixed-precision policy for a training session (paper Fig 14, §6.4).

    ``compute_dtype`` is what fwd/bwd see: params are cast at the
    forward-stage boundary (the Fig-14 ``cast`` op — one cast per step, so a
    sharded master crosses the wire at compute width), while the optimizer
    keeps fp32 *masters* and fp32 moments. ``loss_scale`` is ``None`` (off),
    a static float (the backward seed is ``scale`` instead of ones;
    accumulated grads are unscaled by ``1/scale`` before the norm), or
    ``"dynamic"``: start at ``init_scale``, multiply by ``backoff_factor``
    and skip the update when the grad norm goes non-finite, multiply by
    ``growth_factor`` after ``growth_interval`` consecutive finite steps.
    Masters are always fp32 — that is what makes bf16 compute lossless to
    round-trip (every bf16 value is exactly representable in fp32).
    """

    compute_dtype: str = "bfloat16"       # "float32" | "bfloat16"
    loss_scale: Any = None                # None | float | "dynamic"
    init_scale: float = 2.0 ** 15         # dynamic mode's starting scale
    growth_interval: int = 2000           # finite steps before scale grows
    growth_factor: float = 2.0
    backoff_factor: float = 0.5

    def __post_init__(self):
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unsupported compute_dtype {self.compute_dtype!r} "
                "(use 'float32' or 'bfloat16')")
        ls = self.loss_scale
        if ls is not None and ls != "dynamic":
            if not isinstance(ls, (int, float)) or float(ls) <= 0:
                raise ValueError(
                    f"loss_scale must be None, a positive number, or "
                    f"'dynamic'; got {ls!r}")
        if self.growth_interval < 1:
            raise ValueError("growth_interval must be >= 1")


def loss_scale_update(policy: PrecisionPolicy, scale: float, good_steps: int,
                      grads_finite: bool) -> Tuple[bool, float, int]:
    """One dynamic-loss-scale transition: ``(skip, next_scale, next_good)``.

    Shared by the pipelined ``scale`` actor and the monolithic engine so the
    scale trajectories (and skip decisions) are identical on every backend.
    """
    if not grads_finite:
        return True, float(scale) * float(policy.backoff_factor), 0
    good = int(good_steps) + 1
    if good >= int(policy.growth_interval):
        return False, float(scale) * float(policy.growth_factor), 0
    return False, float(scale), good


@dataclasses.dataclass(frozen=True)
class OptimizerSpec:
    """Pluggable optimizer for staged training programs (SGD or AdamW).

    ``lr`` is either a float or a step-indexed callable ``lr(step) -> float``
    (``step`` counts optimizer steps from 0 — the schedule is resolved on the
    host once per step and broadcast into every stage's update program).
    ``grad_clip`` > 0 enables *global*-norm clipping: the pipeline wires a
    ``norm`` actor that sums per-stage squared-norm partials (P→B boxing
    expressed as an actor) and broadcasts the clip scale back to every
    ``opt{s}``. AdamW carries persistent :class:`repro.optim.adamw.AdamWState`
    (step count, mu, nu) per stage — the second register stream.

    ``zero=True`` (AdamW only) shards that stream ZeRO-style (paper §6.4):
    the optimizer holds flat ``(dp, 1, chunk)`` fp32 master/moment shards
    (:mod:`repro.optim.zero`) instead of dense params + ``AdamWState``, and
    ``update`` takes/returns masters in that layout. ``zero_dp`` is the
    data-axis fold, ``zero_shapes`` the original param shapes the gather
    restores (``api.compile`` records both). ``precision`` adds a
    :class:`PrecisionPolicy` on top — bf16 compute params gathered from fp32
    masters each step, with optional loss scaling.
    """

    kind: str = "sgd"                     # "sgd" | "adamw"
    lr: Any = 1e-2                        # float or fn(step) -> float
    beta1: float = 0.9                    # adamw only below
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 0.0                # 0 disables global-norm clipping
    zero: bool = False                    # ZeRO-shard masters + moments
    zero_dp: int = 1                      # data-axis fold of the flat shards
    zero_shapes: Any = None               # ((name, shape), ...) for gathers
    precision: Optional[PrecisionPolicy] = None

    def __post_init__(self):
        if self.kind not in ("sgd", "adamw"):
            raise ValueError(f"unknown optimizer kind {self.kind!r}")
        if self.zero and self.kind != "adamw":
            raise ValueError(
                "zero=True shards AdamW state; it requires kind='adamw'")
        if self.zero and self.zero_dp < 1:
            raise ValueError(f"zero_dp must be >= 1, got {self.zero_dp}")
        if self.precision is not None and not isinstance(self.precision,
                                                         PrecisionPolicy):
            raise ValueError("precision must be a PrecisionPolicy")
        if (self.precision is not None and self.precision.loss_scale is not None
                and self.precision.compute_dtype == "float32"):
            raise ValueError(
                "loss_scale requires compute_dtype='bfloat16' (fp32 compute "
                "has nothing to rescue from underflow)")

    @classmethod
    def sgd(cls, lr: Any = 1e-2, grad_clip: float = 0.0) -> "OptimizerSpec":
        return cls(kind="sgd", lr=lr, grad_clip=grad_clip)

    @classmethod
    def adamw(cls, lr: Any = 3e-4, beta1: float = 0.9, beta2: float = 0.95,
              eps: float = 1e-8, weight_decay: float = 0.1,
              grad_clip: float = 1.0) -> "OptimizerSpec":
        return cls(kind="adamw", lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                   weight_decay=weight_decay, grad_clip=grad_clip)

    @property
    def stateful(self) -> bool:
        return self.kind == "adamw"

    def lr_at(self, step: int) -> float:
        return float(self.lr(step)) if callable(self.lr) else float(self.lr)

    # -- mixed-precision / ZeRO accessors -----------------------------------

    @property
    def mixed_precision(self) -> bool:
        """True when the optimizer holds explicit fp32 masters (a precision
        policy is set, or ZeRO sharding is on)."""
        return self.precision is not None or self.zero

    @property
    def compute_dtype(self) -> Optional[str]:
        """The dtype fwd/bwd see params in, or None to keep the param dtype
        as given (the legacy no-masters behavior)."""
        if self.precision is not None:
            return self.precision.compute_dtype
        return "float32" if self.zero else None

    @property
    def loss_scaling(self) -> Any:
        """None (off), a static float, or ``"dynamic"``."""
        return None if self.precision is None else self.precision.loss_scale

    @property
    def dynamic_scaling(self) -> bool:
        return self.loss_scaling == "dynamic"

    def initial_scale(self) -> float:
        ls = self.loss_scaling
        if ls is None:
            return 1.0
        if ls == "dynamic":
            return float(self.precision.init_scale)
        return float(ls)

    @property
    def zero_shape_map(self) -> Dict[str, Tuple[int, ...]]:
        """Param name -> original shape, for gathering flat ZeRO shards."""
        if self.zero_shapes is None:
            raise ValueError(
                "OptimizerSpec.zero_shapes is unset; api.compile records the "
                "param shapes when zero=True")
        items = (self.zero_shapes.items()
                 if isinstance(self.zero_shapes, dict) else self.zero_shapes)
        return {n: tuple(int(d) for d in s) for n, s in items}

    def shard_masters(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Full params -> flat fp32 ``(dp, 1, chunk)`` master shards."""
        from repro.optim.zero import shard_flat
        return {n: shard_flat(jnp.asarray(v), dp=self.zero_dp)
                for n, v in params.items()}

    def gather_params(self, masters: Dict[str, Any], dtype: str = "float32",
                      shapes: Optional[Dict[str, Tuple[int, ...]]] = None):
        """Flat master shards -> full params in ``dtype`` (the Fig-14 cast
        happens *before* the reshape-gather, so a bf16 gather moves half the
        bytes of an fp32 one)."""
        from repro.optim.zero import gather_flat
        shapes = self.zero_shape_map if shapes is None else shapes
        return {n: gather_flat(m, shape=tuple(shapes[n]), dtype=dtype)
                for n, m in masters.items()}

    def init_state(self, params: Dict[str, Any]):
        """Fresh optimizer state for ``params`` (None for stateless SGD).

        With ``zero=True``, ``params`` are the *flat master shards* and the
        returned state is a flat :class:`repro.optim.zero.ZeroState`."""
        if self.kind == "sgd":
            return None
        if self.zero:
            from repro.optim.zero import init_zero_flat
            return init_zero_flat(dict(params))
        from repro.optim.adamw import init_adamw
        return init_adamw(dict(params))

    def update(self, params: Dict[str, Any], grads: Dict[str, Any], state,
               lr_now: float):
        """Apply one optimizer step to ``params`` given already-clipped fp32
        ``grads``. Returns ``(new_params, new_state)``.

        Per-tensor math runs through shared jitted kernels
        (:func:`sgd_update` / :func:`repro.optim.adamw.adamw_param_update`),
        so applying this to per-stage param subsets (the opt actors) or to
        the full param dict (the monolithic reference) yields bit-identical
        values tensor by tensor.
        """
        if self.kind == "sgd":
            return {n: sgd_update(params[n], grads[n], lr_now)
                    for n in params}, None
        if self.zero:
            from repro.optim.zero import zero_stage_update
            if state is None:
                state = self.init_state(params)
            return zero_stage_update(
                params, grads, state, lr_now, dp=self.zero_dp,
                beta1=self.beta1, beta2=self.beta2, eps=self.eps,
                weight_decay=self.weight_decay)
        from repro.optim.adamw import AdamWState, adamw_param_update
        if state is None:
            state = self.init_state(params)
        new_step = state.step + 1
        new_p, new_mu, new_nu = {}, {}, {}
        for n in params:
            new_p[n], new_mu[n], new_nu[n] = adamw_param_update(
                params[n], grads[n], state.mu[n], state.nu[n], new_step,
                lr_now, beta1=self.beta1, beta2=self.beta2, eps=self.eps,
                weight_decay=self.weight_decay)
        return new_p, AdamWState(new_step, new_mu, new_nu)

    def split_state(self, state, stage_param_names: Dict[int, Sequence[str]]):
        """Split a merged optimizer state into per-stage states keyed by
        stage index (the snapshot-restore tap: a state saved under one
        stage partition re-splits under another). ``stage_param_names``
        maps stage index -> that stage's param names. Stateless optimizers
        split to None entries."""
        if not self.stateful or state is None:
            return {s: None for s in stage_param_names}
        out = {}
        if self.zero:
            # A merged state is always the *full* AdamWState (load_snapshot
            # gathers shards on the host), so re-splitting is shape-agnostic:
            # shard each stage's moments flat at this spec's dp fold.
            from repro.optim.zero import ZeroState, shard_flat
            for s, names in stage_param_names.items():
                missing = [n for n in names if n not in state.mu]
                if missing:
                    raise ValueError(
                        f"optimizer state missing moments for params {missing}")
                out[s] = ZeroState(
                    state.step,
                    {n: shard_flat(state.mu[n], dp=self.zero_dp)
                     for n in names},
                    {n: shard_flat(state.nu[n], dp=self.zero_dp)
                     for n in names})
            return out
        from repro.optim.adamw import AdamWState
        for s, names in stage_param_names.items():
            missing = [n for n in names if n not in state.mu]
            if missing:
                raise ValueError(
                    f"optimizer state missing moments for params {missing}")
            out[s] = AdamWState(state.step,
                                {n: state.mu[n] for n in names},
                                {n: state.nu[n] for n in names})
        return out

    def merge_states(self, states: Sequence[Any]):
        """Inverse of :meth:`split_state`: merge per-stage states into one
        state over all params (None for a stateless optimizer)."""
        if not self.stateful:
            return None
        from repro.optim.adamw import AdamWState
        states = [s for s in states if s is not None]
        if not states:
            return None
        mu: Dict[str, Any] = {}
        nu: Dict[str, Any] = {}
        if self.zero:
            # Flat per-stage ZeroStates gather back to a full AdamWState so
            # the merged form is partition- and zero-agnostic.
            shapes = self.zero_shape_map
            for st in states:
                mu.update(self.gather_params(st.mu, shapes=shapes))
                nu.update(self.gather_params(st.nu, shapes=shapes))
            return AdamWState(states[0].step, mu, nu)
        for st in states:
            mu.update(st.mu)
            nu.update(st.nu)
        return AdamWState(states[0].step, mu, nu)


def _zero_cot(v):
    """Zero cotangent matching ``v``: zeros for inexact dtypes, a float0
    array for integer outputs (what jax.vjp requires for non-diff outputs)."""
    import numpy as np
    v = jnp.asarray(v)
    if jnp.issubdtype(v.dtype, jnp.inexact):
        return jnp.zeros_like(v)
    return np.zeros(v.shape, dtype=jax.dtypes.float0)


def split_microbatches(inputs: Dict[str, Any], microbatch_names: Sequence[str],
                       num_microbatches: int) -> List[Dict[str, Any]]:
    """Split each named input into ``num_microbatches`` equal chunks along
    axis 0 — one payload dict per microbatch, in version order.

    Both the actor pipeline and the monolithic reference step chunk with this
    one helper so their gradient accumulation orders are bit-identical.
    """
    import numpy as np
    for n in microbatch_names:
        if inputs[n].shape[0] % num_microbatches:
            raise ValueError(
                f"input {n} axis 0 ({inputs[n].shape[0]}) not divisible by "
                f"num_microbatches={num_microbatches}")
    payloads: List[Dict[str, Any]] = [dict() for _ in range(num_microbatches)]
    for n in microbatch_names:
        for k, chunk in enumerate(np.split(np.asarray(inputs[n]),
                                           num_microbatches, axis=0)):
            payloads[k][n] = chunk
    return payloads


def reassemble_sinks(graph: LogicalGraph, sinks: Sequence[LTensor],
                     microbatch_inputs: Sequence[str],
                     per_chunk: Sequence[Dict[str, Any]]) -> Tuple:
    """Reassemble graph sinks from per-microbatch results (the inverse of
    :func:`split_microbatches`), one value per ``sinks`` entry.

    Sinks downstream of a microbatched input are per-chunk slices ->
    concatenate along the batch axis; anything else (e.g. a weights-only
    sink) is recomputed identically every chunk -> take one copy. Shared by
    the actor pipeline and the monolithic backend so the two reassemble
    bit-identically.
    """
    import numpy as np

    mb_dependent = graph.downstream_of(microbatch_inputs)
    results = []
    for t in sinks:
        if t.name in mb_dependent:
            results.append(np.concatenate(
                [np.asarray(d[t.name]) for d in per_chunk], axis=0))
        else:
            results.append(np.asarray(per_chunk[0][t.name]))
    return tuple(results)


def _scatter_args(diff_idx: Sequence[int], nondiff_idx: Sequence[int],
                  n_in: int, diff_vals: Sequence,
                  nondiff_vals: Sequence) -> List:
    """Rebuild a positional argument list from its diff/nondiff partition.

    One helper shared by :func:`lower_train_plan` and
    :func:`lower_train_stages` so the monolithic reference and the pipelined
    stages assemble ``jax.vjp`` arguments identically — the bit-identity
    contract depends on these staying in lockstep.
    """
    args = [None] * n_in
    for i, v in zip(diff_idx, diff_vals):
        args[i] = v
    for i, v in zip(nondiff_idx, nondiff_vals):
        args[i] = v
    return args


def _resolve_loss(graph: LogicalGraph, loss) -> LTensor:
    sinks = graph.sinks()
    if loss is None:
        if len(sinks) != 1:
            raise ValueError(
                f"graph has {len(sinks)} sinks "
                f"({[t.name for t in sinks]}); pass loss= explicitly")
        return sinks[0]
    name = loss.name if isinstance(loss, LTensor) else loss
    for t in sinks:
        if t.name == name:
            return t
    raise ValueError(f"loss {name!r} is not a graph sink "
                     f"(sinks: {[t.name for t in sinks]})")


def _resolve_params(graph: LogicalGraph, params) -> List[LTensor]:
    by_name = {t.name: t for t in graph.inputs}
    out = []
    for p in params:
        name = p.name if isinstance(p, LTensor) else p
        if name not in by_name:
            raise ValueError(f"param {name!r} is not a graph input")
        t = by_name[name]
        if t.dtype not in ("float32", "bfloat16", "float16"):
            raise ValueError(f"param {name!r} has non-float dtype {t.dtype}")
        out.append(t)
    return out


@dataclasses.dataclass
class TrainStageProgram:
    """One pipeline stage of a training graph: forward, backward, interface.

    ``fwd(*values)`` takes one value per ``input_names`` entry and returns
    ``(outputs, vjp)`` — the stage outputs (one per ``output_names``) plus the
    stage's vjp closure. The closure is a jax pytree (``tree_util.Partial``)
    holding the stage-local residuals/activations; the actor runtime stashes
    it in the forward actor's out register so it is recycled exactly when the
    backward actor acks (the paper's stashed-activation register).

    ``bwd(vjp, cotangents)`` takes that closure plus one cotangent per output
    (see :meth:`output_cotangents`) and returns one cotangent per
    ``diff_input_names`` entry: gradients for this stage's params, upstream
    cotangents for boundary activations from earlier stages. ``bwd`` is None
    for a stage with no differentiable inputs.
    """

    index: int
    fwd: Callable
    bwd: Optional[Callable]
    input_names: Tuple[str, ...]
    output_names: Tuple[str, ...]
    diff_input_names: Tuple[str, ...]
    param_names: Tuple[str, ...]
    mesh: object = None
    in_shardings: Optional[Tuple] = None
    cot_shardings: Optional[Dict[str, Any]] = None

    def place_inputs(self, values: Sequence) -> List:
        """Transfer forward boundary values onto this stage's devices (the
        explicit cross-stage send; no-op when all stages share one mesh)."""
        if self.in_shardings is None:
            return list(values)
        return [jax.device_put(v, sh)
                for v, sh in zip(values, self.in_shardings)]

    def output_cotangents(self, outputs: Dict[str, Any],
                          cotangents: Dict[str, Any],
                          loss_name: str, loss_seed=None) -> Tuple:
        """Assemble the vjp seed for this stage: ones for the loss sink (the
        objective is the *sum* of the loss tensor over each microbatch),
        incoming cotangents for outputs consumed downstream, zeros for the
        rest. ``loss_seed`` overrides the ones-seed with a constant (the
        loss-scale: seeding ``scale`` instead of 1 multiplies every cotangent
        by it, which keeps bf16 grads out of the underflow range). Cross-mesh
        cotangents are transferred onto this stage's devices first (the
        explicit backward cross-stage send)."""
        seeds = []
        for name in self.output_names:
            if name == loss_name:
                if loss_seed is None:
                    seeds.append(jnp.ones_like(outputs[name]))
                else:
                    seeds.append(jnp.full_like(outputs[name], loss_seed))
            elif name in cotangents:
                v = cotangents[name]
                if self.cot_shardings is not None and name in self.cot_shardings:
                    v = jax.device_put(v, self.cot_shardings[name])
                seeds.append(v)
            else:
                seeds.append(_zero_cot(outputs[name]))
        return tuple(seeds)


class TrainStagedProgram:
    """A training graph cut into forward / backward / optimizer programs.

    Produced by :func:`lower_train_stages`. ``stages[s]`` holds stage s's
    forward and backward programs; ``opt_update`` is the shared per-tensor
    SGD program (:func:`sgd_update`), and ``optimizer`` is the pluggable
    :class:`OptimizerSpec` (None means the executor's default SGD).
    :meth:`reference_step` is the sequential reference semantics; the
    concurrent actor-driven execution (1F1B from register quotas) lives in
    :class:`repro.runtime.pipeline.TrainPipelineExecutor`.
    """

    def __init__(self, graph: LogicalGraph, plan: Plan,
                 partition: StagePartition, stages: List[TrainStageProgram],
                 loss: LTensor, param_names: Tuple[str, ...],
                 boundary_sbp: Dict[str, NdSbp],
                 optimizer: Optional[OptimizerSpec] = None):
        self.graph, self.plan, self.partition = graph, plan, partition
        self.stages = stages
        self.loss = loss
        self.param_names = param_names
        self.boundary_sbp = boundary_sbp
        self.opt_update = sgd_update
        self.optimizer = optimizer

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    @property
    def loss_name(self) -> str:
        return self.loss.name

    @property
    def input_names(self) -> List[str]:
        return [t.name for t in self.graph.inputs]

    def stage_of_param(self, name: str) -> int:
        for st in self.stages:
            if name in st.param_names:
                return st.index
        raise KeyError(name)

    def reference_step(self, inputs: Dict[str, Any],
                       microbatch_inputs: Sequence[str],
                       num_microbatches: int, lr: float = 1e-2,
                       optimizer: Optional[OptimizerSpec] = None,
                       opt_state=None, step_index: Optional[int] = None):
        """Sequential (non-actor) execution of one training step.

        Runs every microbatch through all forward stages, then all backward
        stages, accumulating gradients in fp32 in microbatch order, and
        applies the optimizer update. Returns ``(loss, grads, new_params)``
        with the same bit-exact semantics as the actor pipeline: the
        objective is the sum of the loss tensor over the whole batch.

        When an optimizer is in play (``optimizer=`` here or set on the
        program), returns ``(loss, grads, new_params, new_state)`` instead —
        ``grads`` post-clip, ``new_state`` None for SGD. Pass the previous
        ``opt_state`` to continue a stateful (AdamW) run; the lr schedule
        resolves at ``step_index`` (default: ``opt_state.step`` when stateful,
        else 0 — a stateless SGD schedule needs ``step_index`` passed
        explicitly on every call after the first).
        """
        chunks = split_microbatches(inputs, microbatch_inputs,
                                    num_microbatches)
        mb_names = set(microbatch_inputs)
        loss_total = None
        grads: Dict[str, Any] = {}
        for chunk in chunks:
            env = {n: (chunk[n] if n in mb_names else inputs[n])
                   for n in self.input_names}
            vjps = {}
            for st in self.stages:
                args = st.place_inputs([env[n] for n in st.input_names])
                outs, vjp = st.fwd(*args)
                env.update(zip(st.output_names, outs))
                vjps[st.index] = vjp
            cots: Dict[str, Any] = {}
            for st in reversed(self.stages):
                if st.bwd is None:
                    continue
                seeds = st.output_cotangents(env, cots, self.loss_name)
                in_cots = st.bwd(vjps[st.index], seeds)
                for name, c in zip(st.diff_input_names, in_cots):
                    if name in st.param_names:
                        c32 = c.astype(jnp.float32)
                        grads[name] = (grads[name] + c32 if name in grads
                                       else c32)
                    else:
                        cots[name] = (cots[name] + c if name in cots else c)
            ls = jnp.sum(env[self.loss_name])
            loss_total = ls if loss_total is None else loss_total + ls
        opt = optimizer if optimizer is not None else self.optimizer
        if opt is not None and (opt.zero or opt.precision is not None):
            raise NotImplementedError(
                "reference_step does not model zero/mixed precision; compare "
                "against the api.compile monolithic backend instead")
        if opt is None:
            new_params = {n: self.opt_update(inputs[n], grads[n], lr)
                          for n in self.param_names}
            return loss_total, grads, new_params
        from repro.optim.adamw import (clip_scale, global_norm_from_partials,
                                       scale_grad, sqnorm_partials)
        if opt.grad_clip:
            norm = global_norm_from_partials(sqnorm_partials(grads),
                                             self.param_names)
            scale = clip_scale(norm, opt.grad_clip)
            grads = {n: scale_grad(g, scale) for n, g in grads.items()}
        if opt.stateful and opt_state is None:
            opt_state = opt.init_state({n: inputs[n]
                                        for n in self.param_names})
        if step_index is None:
            step_index = int(opt_state.step) if opt_state is not None else 0
        new_params, new_state = opt.update(
            {n: inputs[n] for n in self.param_names}, grads, opt_state,
            opt.lr_at(step_index))
        return loss_total, grads, new_params, new_state


def lower_train_plan(graph: LogicalGraph, plan: Plan, mesh, params,
                     loss=None, scaled: bool = False) -> Callable:
    """Monolithic training program — the reference the pipeline is checked
    against. Returns a jitted ``fn(*graph_input_values) -> (loss_vec, grads)``
    where ``loss_vec`` is the (unreduced) loss sink and ``grads`` holds
    ``d(sum(loss_vec))/d(param)`` for each param, in ``params`` order.

    Differentiation seeds ``ones_like(loss_vec)`` exactly like the pipelined
    backward stages, so per-microbatch gradients are bit-identical to the
    composed per-stage vjps. With ``scaled=True`` the returned function takes
    ``fn(loss_seed, *graph_input_values)`` and seeds ``full_like(loss_vec,
    loss_seed)`` instead — the loss-scaling hook, matching the pipelined
    :meth:`TrainStageProgram.output_cotangents` seed exactly.
    """
    loss_t = _resolve_loss(graph, loss)
    param_ts = _resolve_params(graph, params)
    sinks = graph.sinks()
    for t in sinks:
        if plan.tensor_sbp[t.name].has_partial:
            raise ValueError(f"graph output {t.name} planned as partial-value")
    boundary = {t.name: plan.tensor_sbp[t.name]
                for t in list(graph.inputs) + sinks}
    mapped = _lower_subgraph(graph, plan, mesh, graph.topo_ops(),
                             graph.inputs, sinks, boundary, boundary)
    loss_pos = [t.name for t in sinks].index(loss_t.name)
    n_in = len(graph.inputs)
    diff_idx = [i for i, t in enumerate(graph.inputs)
                if t.name in {p.name for p in param_ts}]
    # keep grads in the caller's `params` order, not graph-input order
    order = {graph.inputs[i].name: j for j, i in enumerate(diff_idx)}
    perm = [order[p.name] for p in param_ts]

    nondiff_idx = [i for i in range(n_in) if i not in set(diff_idx)]

    def value_and_grad(*all_ins):
        diff_vals = [all_ins[i] for i in diff_idx]
        nondiff_vals = [all_ins[i] for i in nondiff_idx]

        def f(*dv):
            return mapped(*_scatter_args(diff_idx, nondiff_idx, n_in, dv,
                                         nondiff_vals))[loss_pos]

        loss_vec, vjp = jax.vjp(f, *diff_vals)
        raw = vjp(jnp.ones_like(loss_vec))
        return loss_vec, tuple(raw[j] for j in perm)

    def value_and_grad_scaled(loss_seed, *all_ins):
        diff_vals = [all_ins[i] for i in diff_idx]
        nondiff_vals = [all_ins[i] for i in nondiff_idx]

        def f(*dv):
            return mapped(*_scatter_args(diff_idx, nondiff_idx, n_in, dv,
                                         nondiff_vals))[loss_pos]

        loss_vec, vjp = jax.vjp(f, *diff_vals)
        raw = vjp(jnp.full_like(loss_vec, loss_seed))
        return loss_vec, tuple(raw[j] for j in perm)

    return jax.jit(value_and_grad_scaled if scaled else value_and_grad)


def lower_train_stages(graph: LogicalGraph, plan: Plan,
                       partition: StagePartition, params, loss=None,
                       mesh=None, stage_meshes: Optional[Sequence] = None,
                       optimizer: Optional[OptimizerSpec] = None
                       ) -> TrainStagedProgram:
    """Cut a training graph into forward / backward / optimizer programs.

    Builds on :func:`lower_stages`' forward partition: each stage's lowered
    shard_map program is differentiated with ``jax.vjp`` over its
    *differentiable* inputs — the stage-local params plus any boundary
    activations derived from params. Residuals stay inside the per-stage vjp
    closure (stage-local); only cotangents cross stage boundaries, flowing
    backward along the same seams the activations flowed forward.

    ``params`` names the graph inputs to be trained; each must be consumed by
    ops of exactly one stage (pipeline parallelism shards params by stage).
    ``loss`` names the graph sink to differentiate (default: the sole sink).
    ``mesh`` / ``stage_meshes`` as in :func:`lower_stages`. ``optimizer`` is
    an optional :class:`OptimizerSpec` carried on the program (the executor
    falls back to plain SGD when absent).
    """
    meshes = _resolve_meshes(partition, mesh, stage_meshes)
    loss_t = _resolve_loss(graph, loss)
    param_ts = _resolve_params(graph, params)
    param_names = {t.name for t in param_ts}

    for p in param_ts:
        stages_using = {partition.stage_of[c.name]
                        for c in graph.consumers(p)}
        if len(stages_using) != 1:
            raise ValueError(
                f"param {p.name!r} is consumed by stages "
                f"{sorted(stages_using)}; pipeline training requires each "
                "param to live on exactly one stage")

    requires_grad = graph.downstream_of(param_names)
    loss_anc = graph.ancestors(loss_t)
    for p in param_ts:
        if p.name not in loss_anc:
            raise ValueError(
                f"param {p.name!r} does not feed the loss {loss_t.name!r}; "
                "its gradient would be identically zero — drop it from "
                "params or pick the right loss sink")

    def diff(name: str) -> bool:
        return name in requires_grad and name in loss_anc

    _, boundary_sbp, interfaces = _stage_interfaces(graph, plan, partition)

    stages: List[TrainStageProgram] = []
    for s, iface in enumerate(interfaces):
        mapped = _lower_subgraph(graph, plan, meshes[s], iface.ops,
                                 iface.in_tensors, iface.out_tensors,
                                 iface.in_sbp, iface.out_sbp)
        in_names = tuple(t.name for t in iface.in_tensors)
        n_in = len(in_names)
        diff_idx = [i for i, t in enumerate(iface.in_tensors)
                    if diff(t.name)]
        nondiff_idx = [i for i in range(n_in) if i not in set(diff_idx)]
        diff_in = tuple(in_names[i] for i in diff_idx)
        stage_params = tuple(n for n in diff_in if n in param_names)

        if diff_idx:
            def fwd_py(*ins, _mapped=mapped, _diff=tuple(diff_idx),
                       _nondiff=tuple(nondiff_idx), _n=n_in):
                diff_vals = [ins[i] for i in _diff]
                nondiff_vals = [ins[i] for i in _nondiff]

                def f(*dv):
                    return _mapped(*_scatter_args(_diff, _nondiff, _n, dv,
                                                  nondiff_vals))

                return jax.vjp(f, *diff_vals)

            fwd = jax.jit(fwd_py)
            bwd = jax.jit(lambda vjp, cots: vjp(cots))
        else:
            fwd = jax.jit(lambda *ins, _mapped=mapped: (_mapped(*ins), None))
            bwd = None

        in_shardings = None
        cot_shardings = None
        if stage_meshes is not None:
            in_shardings = _boundary_shardings(
                graph.placement, meshes[s], iface.in_tensors, iface.in_sbp)
            cot_shardings = dict(zip(
                (t.name for t in iface.out_tensors),
                _boundary_shardings(graph.placement, meshes[s],
                                    iface.out_tensors, iface.out_sbp)))
        stages.append(TrainStageProgram(
            index=s, fwd=fwd, bwd=bwd,
            input_names=in_names,
            output_names=tuple(t.name for t in iface.out_tensors),
            diff_input_names=diff_in, param_names=stage_params,
            mesh=meshes[s], in_shardings=in_shardings,
            cot_shardings=cot_shardings))

    all_params = tuple(p.name for p in param_ts)
    return TrainStagedProgram(graph, plan, partition, stages, loss_t,
                              all_params, boundary_sbp, optimizer=optimizer)


# ---------------------------------------------------------------------------
# Serve lowering (paper §4.3 applied to serving): the autoregressive decode
# step cut into per-stage jitted programs. Stage s owns a contiguous slice of
# the layer stack; its KV/SSM caches never leave the stage — they are a
# persistent stage-local register stream (the same pattern as the optimizer
# state in training pipelines), updated in place by every decode fire. The
# request-admission runtime half lives in repro.runtime.pipeline
# (ServePipelineExecutor).
# ---------------------------------------------------------------------------

class MoeCounts:
    """A serve stage's running sum of its programs' expert-layer counts,
    added on the device (no host sync) until :meth:`take` reads it."""

    def __init__(self):
        self.total = None

    def add(self, counts) -> None:
        self.total = counts if self.total is None else self.total + counts

    def take(self):
        """The sum since the last take (``None`` if nothing was added),
        and a fresh start."""
        total, self.total = self.total, None
        return total


@dataclasses.dataclass
class ServeStage:
    """One lowered decode/prefill pipeline stage.

    ``decode(params, caches, xin, pos) -> (xout, new_caches)``: one token for
    a full slot group. ``xin`` is the token ids (B,) on the first stage, the
    hidden (B, 1, d) elsewhere; ``xout`` is the model-sharded logits
    (B, padded_vocab) on the last stage, the hidden elsewhere.

    ``prefill(params, xin, last_index) -> (xout, slot_caches)``: run one
    admitted request's prompt (batch-replicated, typically B=1) through the
    slice and build its caches; the last stage returns the first-token logits
    gathered at ``last_index`` (the prompt's final position) through the SAME
    head math as ``decode``. ``init_caches(tok) -> caches`` allocates the
    zeroed group cache; ``write_slot(caches, slot_caches, slot)`` scatters a
    freshly prefilled request into slot ``slot`` of the group cache.

    ``chunk(params, caches, xin, pos0, adv) -> (stacked_out, new_caches)``:
    one bounded chunked-prefill step — a ``lax.scan`` of the decode step
    over ``xin``'s leading chunk axis, slot ``b`` visiting positions
    ``pos0[b] + t * adv[b]`` (parked slots pass ``adv == 0``). The stacked
    output's last entry is the decode output at the chunk's final position,
    so the final chunk's logits feed first-token sampling through the same
    head math as ``decode``.

    With ``moe`` (the slice holds an expert layer) each of the three
    programs also returns, last, the int32 counts
    :data:`repro.models.mlp.MOE_COUNTERS` of the fire, summed over the
    slice's layers and the mesh; :func:`split_moe_counts` takes them off
    into ``moe_counts``.
    """

    index: int
    decode: Callable
    prefill: Callable
    init_caches: Callable
    write_slot: Callable
    params: Dict[str, Any]
    units: Tuple[int, int]              # [lo, hi) over prologue+period units
    first: bool
    last: bool
    mesh: object = None
    chunk: Callable = None
    moe: bool = False
    moe_counts: MoeCounts = dataclasses.field(default_factory=MoeCounts)


def split_moe_counts(stage: ServeStage, out):
    """A stage program's outputs without its expert-layer counts, which are
    added to ``stage.moe_counts`` (programs of stages without an expert
    layer return none)."""
    if not stage.moe:
        return out
    stage.moe_counts.add(out[-1])
    return out[:-1]


def take_moe_counts(stages) -> Optional[Dict[str, int]]:
    """The expert-layer counts of ``stages`` since the last take, summed
    and read to the host once, by :data:`repro.models.mlp.MOE_COUNTERS`
    name; ``None`` where none were added."""
    import numpy as np

    from repro.models.mlp import MOE_COUNTERS

    parts = [st.moe_counts.take() for st in stages]
    parts = [np.asarray(x, np.int64) for x in parts if x is not None]
    if not parts:
        return None
    return dict(zip(MOE_COUNTERS, (int(v) for v in sum(parts))))


class ServeStagedProgram:
    """A pipeline of independently-jitted decode-stage programs.

    Built by :func:`lower_serve_stages`; run sequentially (num_stages == 1 is
    the monolithic serve engine) or concurrently by
    :class:`repro.runtime.pipeline.ServePipelineExecutor`, one actor per
    stage, with caches as stage-local persistent state.
    """

    def __init__(self, cfg, plan, mesh, stages: List[ServeStage],
                 cache_len: int, max_prompt_len: int, group_size: int):
        self.cfg = cfg
        self.plan = plan
        self.mesh = mesh
        self.stages = stages
        self.cache_len = cache_len
        self.max_prompt_len = max_prompt_len
        self.group_size = group_size

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    #: uniform with Staged/TrainStagedProgram for _StagedExecutorBase
    input_names: Tuple[str, ...] = ()

    def describe(self) -> str:
        lines = [f"serve pipeline: {self.num_stages} stages over "
                 f"{self.stages[-1].units[1]} stack units "
                 f"(cache_len={self.cache_len}, "
                 f"group_size={self.group_size})"]
        for st in self.stages:
            extra = []
            if st.first:
                extra.append("embed")
            if st.last:
                extra.append("final_norm+head")
            lines.append(f"  stage {st.index}: units "
                         f"[{st.units[0]}, {st.units[1]})"
                         + (f" + {'+'.join(extra)}" if extra else ""))
        return "\n".join(lines)


def _serve_subtree(tree, lo: int, hi: int, n_pro: int, slice_periods: bool):
    """Slice a {"prologue": [...], "body": [per-slot stacked trees]} pytree
    to units [lo, hi). ``slice_periods`` slices the stacked leading period
    dim (params/caches); spec trees keep their per-slot entries whole."""
    pro = list(tree["prologue"][lo:min(hi, n_pro)])
    plo, phi = max(lo - n_pro, 0), max(hi - n_pro, 0)
    body = []
    if phi > plo:
        if slice_periods:
            body = [jax.tree.map(lambda a: a[plo:phi], slot)
                    for slot in tree["body"]]
        else:
            body = list(tree["body"])
    return {"prologue": pro, "body": body}


def lower_serve_stages(cfg, mesh, params: Dict[str, Any], num_stages: int,
                       cache_len: int, max_prompt_len: int, group_size: int,
                       sliding_window: int = 0) -> ServeStagedProgram:
    """Cut the decode step of a :class:`repro.configs.base.ModelConfig`
    model into ``num_stages`` jitted stage programs (stage = contiguous
    slice of the layer stack; tensor parallelism via shard_map *inside*
    every stage, exactly like :func:`repro.train.steps.make_serve_step`).

    ``params`` are the full model params (as built by
    ``repro.models.model_zoo.build_model(cfg, plan).init``); each stage gets
    its prologue blocks, plus the embedding on the first stage and the final
    norm + unembedding head on the last. The stacked body is placed on the
    mesh once and shared whole: a stage's programs scan its own range of
    periods, so the stages together hold one copy of the model.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import transformer as T
    from repro.models.common import MeshPlan
    from repro.models.model_zoo import cache_specs, make_decode_caches

    if cfg.encoder_decoder or cfg.embed_frontend:
        raise ValueError(
            f"{cfg.name}: pipelined serving needs a token frontend "
            "(encoder-decoder / embed-frontend archs are not supported)")
    plan = MeshPlan(tuple(mesh.axis_names), tuple(mesh.devices.shape))
    if cache_len < 2:
        # retired/empty slots decode a dummy token "parked" at the reserved
        # position cache_len - 1; with cache_len < 2 that position would
        # collide with position 0 of every live request's window
        raise ValueError(
            f"cache_len={cache_len} must be >= 2: the final cache position "
            "(cache_len - 1) is reserved as the parking slot for "
            "retired/empty decode slots")
    if cache_len % plan.tp:
        raise ValueError(f"cache_len={cache_len} must be divisible by the "
                         f"model-parallel degree {plan.tp}")
    if group_size % plan.dp:
        raise ValueError(f"group_size={group_size} must be divisible by the "
                         f"data-parallel degree {plan.dp}")

    def _mesh_sum(counts, axes):
        for ax in axes:
            if plan.axis_size(ax) > 1:
                counts = jax.lax.psum(counts, ax)
        return counts

    lay = T.stack_layout(cfg)
    n_pro = len(lay.prologue)
    n_units = n_pro + lay.n_periods
    if not (1 <= num_stages <= n_units):
        raise ValueError(f"num_stages={num_stages} must be in [1, {n_units}] "
                         f"(= prologue blocks + body periods for {cfg.name})")

    dp = plan.data_axes if len(plan.data_axes) > 1 else plan.data_axes[0]
    mx = plan.model_axis
    pspecs_full = T.model_specs(cfg, plan)
    cspecs_grp = cache_specs(cfg, plan, plan.data_axes)
    cspecs_one = cache_specs(cfg, plan, ())
    adt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}[cfg.dtype]

    # contiguous unit ranges, balanced by count
    sizes = [n_units // num_stages + (1 if s < n_units % num_stages else 0)
             for s in range(num_stages)]
    bounds, lo = [], 0
    for sz in sizes:
        bounds.append((lo, lo + sz))
        lo += sz

    def place(tree, specs):
        # on the mesh, once (free where the arrays already sit there), so
        # no call reshards them
        return jax.device_put(tree, jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), specs,
            is_leaf=lambda x: isinstance(x, P)))

    # every stage that runs a period shares this one placed body
    body = place(params["body"], pspecs_full["body"])

    stages: List[ServeStage] = []
    for s, (lo, hi) in enumerate(bounds):
        first, last = s == 0, s == num_stages - 1
        pro_kinds = lay.prologue[lo:min(hi, n_pro)]
        periods = (max(lo - n_pro, 0), max(hi - n_pro, 0))
        sparams = {"prologue": list(params["prologue"][lo:min(hi, n_pro)])}
        moe = any(m == "moe" for _, m in pro_kinds) or (
            hi > n_pro and any(m == "moe" for _, m in lay.period_slots))
        sspecs = _serve_subtree(pspecs_full, lo, hi, n_pro, False)
        grp_cspecs = _serve_subtree(cspecs_grp, lo, hi, n_pro, False)
        one_cspecs = _serve_subtree(cspecs_one, lo, hi, n_pro, False)
        if first:
            sparams["embed"] = params["embed"]
            sspecs["embed"] = pspecs_full["embed"]
        if last:
            for k in ("final_norm", "unembed"):
                sparams[k] = params[k]
                sspecs[k] = pspecs_full[k]
        sparams = place(sparams, {k: sspecs[k] for k in sparams})
        sparams["body"] = body if periods[1] > periods[0] else []

        def local_decode(p, caches, xin, pos, _first=first, _last=last,
                         _kinds=pro_kinds, _periods=periods, _moe=moe):
            if _first:
                x = T.embed_tokens(p["embed"], xin[:, None], plan).astype(adt)
            else:
                x = xin
            x, new_caches, counts = T.decode_stack_slice(
                p, caches, x, pos, cfg, plan, _kinds, _periods,
                sliding_window=sliding_window)
            if _last:
                x = T.rms_norm(x, p["final_norm"].astype(x.dtype),
                               cfg.norm_eps)
                out = x[:, 0] @ p["unembed"].astype(x.dtype)
            else:
                out = x
            if _moe:      # every device's tokens and held experts
                return out, new_caches, _mesh_sum(counts, plan.axis_names)
            return out, new_caches

        # stage-distinct program names, so a device trace tells stage 0's
        # programs from stage 1's (jit names its module after the function)
        local_decode.__name__ = f"local_decode_s{s}"

        xin_spec = P(dp)                 # token ids (B,) or hidden (B, 1, d)
        xout_spec = P(dp, mx) if last else P(dp)
        counts_spec = (P(),) if moe else ()
        decode = jax.jit(shard_map(
            local_decode, mesh=mesh,
            in_specs=(sspecs, grp_cspecs, xin_spec, P(dp)),
            out_specs=(xout_spec, grp_cspecs) + counts_spec, check=False))

        def local_prefill(p, xin, last_index, _first=first, _last=last,
                          _kinds=pro_kinds, _periods=periods, _moe=moe):
            if _first:
                x = T.embed_tokens(p["embed"], xin, plan).astype(adt)
            else:
                x = xin
            positions = jnp.arange(x.shape[1])
            x, caches, counts = T.prefill_stack_slice(
                p, x, positions, cfg, plan, _kinds, _periods, cache_len,
                sliding_window=sliding_window)
            if _last:
                x = T.rms_norm(x, p["final_norm"].astype(x.dtype),
                               cfg.norm_eps)
                idx = jnp.broadcast_to(last_index[:, None, None],
                                       (x.shape[0], 1, x.shape[-1]))
                h = jnp.take_along_axis(x, idx, axis=1)
                out = h[:, 0] @ p["unembed"].astype(x.dtype)
            else:
                out = x
            if _moe:      # the prompt is replicated over the data axes
                return out, caches, _mesh_sum(counts, (mx,))
            return out, caches

        local_prefill.__name__ = f"local_prefill_s{s}"

        pre_out_spec = P(None, mx) if last else P()
        prefill = jax.jit(shard_map(
            local_prefill, mesh=mesh,
            in_specs=(sspecs, P(), P()),
            out_specs=(pre_out_spec, one_cspecs) + counts_spec,
            check=False))

        def local_chunk(p, caches, xin, pos0, adv, _ld=local_decode,
                        _moe=moe):
            # chunked prefill: scan the decode step over the chunk axis —
            # slot b visits pos0[b] + t * adv[b] (parked slots: adv == 0)
            def step(caches, inp):
                xt, t = inp
                out = _ld(p, caches, xt, pos0 + t * adv)
                return out[1], (out[0],) + out[2:]

            ts = jnp.arange(xin.shape[0], dtype=jnp.int32)
            caches, outs = jax.lax.scan(step, caches, (xin, ts))
            if _moe:
                return outs[0], caches, outs[1].sum(0)
            return outs[0], caches

        local_chunk.__name__ = f"local_chunk_s{s}"

        chunk_out_spec = P(None, dp, mx) if last else P(None, dp)
        chunk = jax.jit(shard_map(
            local_chunk, mesh=mesh,
            in_specs=(sspecs, grp_cspecs, P(None, dp), P(dp), P(dp)),
            out_specs=(chunk_out_spec, grp_cspecs) + counts_spec,
            check=False))

        def local_init(tok, _lo=lo, _hi=hi):
            full = make_decode_caches(cfg, plan, tok.shape[0], cache_len)
            return _serve_subtree(full, _lo, _hi, n_pro, True)

        init_caches = jax.jit(shard_map(
            local_init, mesh=mesh, in_specs=(P(dp),),
            out_specs=grp_cspecs, check=False))

        def write_slot(caches, slot_caches, slot: int):
            # prologue leaves are (B, ...); body leaves are stacked over
            # periods, (periods, B, ...) — the batch slot is axis 1 there
            pro = jax.tree.map(
                lambda gc, sc: gc.at[slot].set(sc[0].astype(gc.dtype)),
                caches["prologue"], slot_caches["prologue"])
            body = jax.tree.map(
                lambda gc, sc: gc.at[:, slot].set(sc[:, 0].astype(gc.dtype)),
                caches["body"], slot_caches["body"])
            return {"prologue": pro, "body": body}

        stages.append(ServeStage(
            index=s, decode=decode, prefill=prefill,
            init_caches=init_caches, write_slot=write_slot,
            params=sparams, units=(lo, hi), first=first, last=last,
            mesh=mesh, chunk=chunk, moe=moe))
    return ServeStagedProgram(cfg, plan, mesh, stages, cache_len,
                              max_prompt_len, group_size)
