"""Pipeline-parallel schedules from register quotas (paper §4.3, §6.5).

The paper's key observation: a synchronous pipeline schedule is not a special
scheduler — it *emerges* from out-register quotas. A stage's forward actor
output register is referenced by BOTH the next stage's forward AND this
stage's backward (the stashed activation); it is recycled only when both have
acked. Capping the quota at ``R`` bounds in-flight microbatches to ``R``:

* ``R = num_microbatches``  -> GPipe-style all-forward-then-backward memory;
* ``R = num_stages - stage``-> 1F1B steady state (Megatron's schedule);
* ``R = 1``                 -> fully serialized (no pipelining).

:func:`pipeline_specs` builds the actor graph; :func:`plan_registers` is the
compile-time resource planner: it simulates quotas and picks the smallest one
within ``tolerance`` of the best makespan — this is the "resource planning at
compile time" the paper argues for (§2.3), done with the actor model itself.

Three executors then run *real compiled programs* under that protocol:

* :func:`stage_actor_specs` / :class:`ActorPipelineExecutor` — forward-only
  pipelines over the per-stage jitted programs of
  :func:`repro.core.lowering.lower_stages` (inference / PR 1).
* :func:`train_stage_actor_specs` / :class:`TrainPipelineExecutor` — full
  training pipelines over :func:`repro.core.lowering.lower_train_stages`:
  forward actors stash their vjp closure (residuals/activations) in the out
  register that the *backward* actor also references, backward actors flow
  cotangents up the chain, accumulation actors (``emit_every`` — OneFlow's
  `acc` op) sum per-microbatch gradients, and optimizer actors fire once per
  step. The 1F1B schedule is never written down: it emerges from the forward
  quota ``R[s] = num_stages - s`` alone (§4.3, §6.5).
* :func:`serve_stage_actor_specs` / :class:`ServePipelineExecutor` —
  continuous-batching decode with per-stage caches as actor-local state.

Every executor builds its actor graph ONCE (a picklable *spec builder*) and
drives it through the :class:`repro.runtime.base.Runtime` seam: actors are
resettable state machines, each run/step/round is one *epoch* over the same
graph, with per-epoch inputs delivered via ``ctx`` (routed to
``ActorSpec.on_epoch`` hooks) and per-epoch fire bounds via ``fires``.
Persistent per-stage state — placed params, optimizer state, serve caches —
lives in the actor closures, resident wherever the actor runs. Stage ``s``
is addressed at node ``s + 1`` (data/admit/norm at node 0), so under
``runtime="processes"`` each stage owns a real worker process and payloads
cross stages as serialized host arrays (:func:`repro.runtime.base
.encode_payload`) while same-node registers stay zero-copy.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.runtime.actor import ActorSpec
from repro.runtime.base import RUNTIME_KINDS, make_runtime
from repro.runtime.scheduler import CommModel, simulate


def _validate_regs(regs: Sequence[int], num_stages: int,
                   num_microbatches: Optional[int] = None) -> List[int]:
    """Reject bad quota lists up front: a zero/negative quota would deadlock
    (or be silently rewritten), so fail fast naming the offending stage and
    the analyzer's minimal feasible quota vector."""
    regs = list(regs)
    if len(regs) != num_stages:
        raise ValueError(f"need {num_stages} register quotas, got {len(regs)}")
    for s, r in enumerate(regs):
        if r < 1:
            from repro.analysis.deadlock import min_feasible_stage_regs
            feasible = min_feasible_stage_regs(num_stages, num_microbatches)
            raise ValueError(
                f"stage {s} register quota must be >= 1, got {r} "
                f"(regs={regs}); minimal feasible quotas for "
                f"{num_stages} stages: {feasible}")
    return regs


def pipeline_specs(num_stages: int, num_microbatches: int,
                   fwd_time: float = 1.0, bwd_time: float = 2.0,
                   regs: Optional[Sequence[int]] = None,
                   act_nbytes: int = 1 << 20) -> List[ActorSpec]:
    """Actor graph for a synchronous fwd/bwd pipeline over ``num_stages``
    devices. ``regs[s]`` is stage s's activation register quota."""
    if regs is None:
        regs = [num_stages - s for s in range(num_stages)]  # 1F1B default
    regs = _validate_regs(regs, num_stages, num_microbatches)
    specs: List[ActorSpec] = []
    specs.append(ActorSpec(
        name="data", fn=lambda *a: 0, inputs=(), out_regs=2,
        node=0, thread=0, duration=fwd_time * 0.1,
        max_fires=num_microbatches, out_nbytes=act_nbytes))
    for s in range(num_stages):
        fwd_in = "data" if s == 0 else f"f{s-1}"
        # forward actor on device/thread s
        specs.append(ActorSpec(
            name=f"f{s}", fn=lambda *a: 0, inputs=(fwd_in,),
            out_regs=regs[s], node=0, thread=s + 1,
            duration=fwd_time, max_fires=num_microbatches,
            out_nbytes=act_nbytes))
    for s in reversed(range(num_stages)):
        # backward actor: consumes stashed activation f{s} and upstream grad
        ins = (f"f{s}",) if s == num_stages - 1 else (f"f{s}", f"b{s+1}")
        specs.append(ActorSpec(
            name=f"b{s}", fn=lambda *a: 0, inputs=ins,
            out_regs=2, node=0, thread=s + 1,
            duration=bwd_time, max_fires=num_microbatches,
            out_nbytes=act_nbytes))
    # optimizer actor per stage consuming the gradient stream
    for s in range(num_stages):
        specs.append(ActorSpec(
            name=f"opt{s}", fn=lambda *a: 0, inputs=(f"b{s}",),
            out_regs=1, node=0, thread=s + 1, duration=0.01,
            max_fires=num_microbatches))
    return specs


@dataclasses.dataclass
class PipelinePlan:
    """Result of simulating one register-quota choice: the quota itself, the
    simulated makespan, per-stage peak activation registers actually used,
    and the pipeline-bubble fraction (idle time vs the ideal makespan)."""

    regs: List[int]
    makespan: float
    peak_activation_regs: Dict[str, int]
    bubble_fraction: float


def analyze(num_stages: int, num_microbatches: int, regs: Sequence[int],
            fwd_time: float = 1.0, bwd_time: float = 2.0) -> PipelinePlan:
    """Simulate the fwd/bwd pipeline under quota ``regs`` and summarize it
    as a :class:`PipelinePlan`. Raises if the quota deadlocks the graph."""
    specs = pipeline_specs(num_stages, num_microbatches, fwd_time, bwd_time,
                           list(regs))
    res = simulate(specs, comm=CommModel(same_node=0.0, cross_node_latency=0.0))
    if res.deadlocked:
        raise RuntimeError(f"pipeline deadlocked with regs={list(regs)}")
    ideal = num_microbatches * (fwd_time + bwd_time)
    bubble = 1.0 - ideal / res.makespan if res.makespan > 0 else 0.0
    return PipelinePlan(
        regs=list(regs), makespan=res.makespan,
        peak_activation_regs={f"f{s}": res.peak_regs[f"f{s}"]
                              for s in range(num_stages)},
        bubble_fraction=max(0.0, bubble))


def plan_registers(num_stages: int, num_microbatches: int,
                   fwd_time: float = 1.0, bwd_time: float = 2.0,
                   tolerance: float = 0.02) -> PipelinePlan:
    """Compile-time resource planning: smallest uniform quota whose makespan
    is within ``tolerance`` of the best observed — memory saved for free."""
    best: Optional[PipelinePlan] = None
    plans = []
    for r in range(1, num_microbatches + 1):
        p = analyze(num_stages, num_microbatches, [r] * num_stages,
                    fwd_time, bwd_time)
        plans.append(p)
        if best is None or p.makespan < best.makespan:
            best = p
        if r >= num_stages and p.makespan <= best.makespan * (1 + 1e-9):
            break  # saturated: more registers cannot help
    target = best.makespan * (1 + tolerance)
    for p in plans:
        if p.makespan <= target:
            return p
    return best


# ---------------------------------------------------------------------------
# Actor-driven execution of lowered stage programs (compiler ∘ runtime).
#
# This is the seam the paper argues for: the compiler's per-stage jitted
# callables (repro.core.lowering.lower_stages) become real ActorSpec.fn
# bodies. One actor per stage, owned by node s+1 of the runtime; microbatch
# payloads flow through Req.payload as {tensor name: value} dicts along the
# stage chain; out-register quotas alone bound in-flight microbatches, so
# 1F1B-style overlap *emerges* (§4.3) instead of being scheduled explicitly.
# ---------------------------------------------------------------------------

def check_run_inputs(provided, expected, what: str = "input",
                     owned: Sequence[str] = ()) -> None:
    """Fail fast with the offending key when a run/step input dict has
    unknown or missing names, instead of failing deep inside an actor body.

    ``expected`` are the names the caller must provide; ``owned`` are names
    the executor itself supplies (trainable params) — passing one of those is
    reported as such rather than as merely "unknown".
    """
    expected = set(expected)
    owned = set(owned)
    provided = set(provided)
    shadowed = sorted(provided & owned)
    if shadowed:
        raise ValueError(
            f"{what} {shadowed[0]!r} is a trainable param owned by the "
            f"executor; pass only data inputs (expected: {sorted(expected)})")
    unknown = sorted(provided - expected)
    if unknown:
        more = f" (+{len(unknown) - 1} more)" if len(unknown) > 1 else ""
        raise ValueError(
            f"unknown {what} {unknown[0]!r}{more}; "
            f"expected {what}s: {sorted(expected)}")
    missing = sorted(expected - provided)
    if missing:
        more = f" (+{len(missing) - 1} more)" if len(missing) > 1 else ""
        raise ValueError(
            f"missing {what} {missing[0]!r}{more}; "
            f"expected {what}s: {sorted(expected)}")


class _SpecBuilderBase:
    """Base of the picklable spec builders the executors hand to
    :func:`repro.runtime.base.make_runtime`.

    Carries either an already-lowered program (``staged``, process-local —
    what ``runtime="threads"`` uses directly) or a lowering recipe
    (:mod:`repro.runtime.recipes`, pure data). Pickling for a worker process
    drops the lowered program and ships the recipe; the worker re-lowers on
    arrival and jit-compiles only the stages it fires.
    """

    def __init__(self, staged=None, recipe=None):
        if staged is None and recipe is None:
            raise ValueError("spec builder needs a lowered program or a "
                             "lowering recipe")
        self._staged = staged
        self.recipe = recipe

    @property
    def staged(self):
        if self._staged is None:
            self._staged = self.recipe.lower()
        return self._staged

    def __getstate__(self):
        if self.recipe is None:
            raise ValueError(
                "this spec builder carries only a process-local lowered "
                "program; runtime='processes' needs a lowering recipe "
                "(repro.runtime.recipes) — compile through repro.api")
        state = dict(self.__dict__)
        state["_staged"] = None      # workers re-lower from the recipe
        return state


class _StagedExecutorBase:
    """Shared machinery of the stage-pipeline executors.

    Construction-time validation (microbatch count, register-quota length,
    microbatch input names, runtime kind), run-time input validation
    (:func:`check_run_inputs`), and the persistent runtime underneath: the
    executor builds ONE :class:`repro.runtime.base.Runtime` from its spec
    builder on first use and re-runs it per step/round (one epoch each),
    with per-epoch values delivered through ``ctx``/``fires``. Per-run
    instrumentation (``last_makespan``, ``last_history``, ``last_peak_regs``,
    ``last_edge_bytes``) snapshots the most recent epoch.
    """

    def __init__(self, program, microbatch_inputs: Sequence[str],
                 num_microbatches: int, regs: Optional[Sequence[int]],
                 fn_wrap: Optional[Callable] = None,
                 runtime: str = "threads", recipe=None, faults=None):
        if num_microbatches < 1:
            raise ValueError(
                f"num_microbatches must be >= 1, got {num_microbatches}")
        if regs is not None:
            regs = _validate_regs(regs, program.num_stages, num_microbatches)
        for n in microbatch_inputs:
            if n not in program.input_names:
                raise ValueError(f"{n} is not a graph input")
        if runtime not in RUNTIME_KINDS:
            raise ValueError(
                f"unknown runtime {runtime!r}; expected one of "
                f"{RUNTIME_KINDS}")
        if runtime == "processes" and recipe is None:
            raise ValueError(
                "runtime='processes' needs a picklable lowering recipe "
                "(repro.runtime.recipes) — compile through repro.api, or "
                "pass recipe=")
        self.microbatch_inputs = list(microbatch_inputs)
        self.num_microbatches = num_microbatches
        self.regs = regs
        self.fn_wrap = fn_wrap
        self.runtime_kind = runtime
        self.recipe = recipe
        self.faults = faults          # optional chaos FaultPlan (tests/CI)
        # optional repro.analysis.trace.TraceRecorder — set before the first
        # run; the threads runtime logs every Req delivery into it so
        # repro.analysis.trace.check_trace can certify the resequencer
        self.trace = None
        self._rt = None
        self.last_makespan: Optional[float] = None
        self.last_history: Dict[str, List[Tuple[float, float]]] = {}
        self.last_peak_regs: Dict[str, int] = {}
        self.last_edge_bytes: Dict[Tuple[str, str], int] = {}

    def _make_builder(self):
        raise NotImplementedError

    @property
    def runtime(self):
        """The persistent :class:`repro.runtime.base.Runtime` underneath
        (built on first use)."""
        if self._rt is None:
            self._rt = make_runtime(self.runtime_kind, self._make_builder(),
                                    faults=self.faults, trace=self.trace)
        return self._rt

    def _run_rt(self, ctx, fires, timeout: float):
        """Run one epoch over the persistent runtime, snapshotting
        wall-clock makespan, per-actor action history, peak out-registers,
        and per-edge payload traffic."""
        rt = self.runtime
        t0 = time.perf_counter()
        outs = rt.run(ctx=ctx, fires=fires, timeout=timeout)
        self.last_makespan = time.perf_counter() - t0
        self.last_history = dict(rt.last_history)
        self.last_peak_regs = dict(rt.last_peak_regs)
        self.last_edge_bytes = dict(rt.last_edge_bytes)
        return outs

    def close(self) -> None:
        """Release the runtime's workers (threads or processes). The
        executor rebuilds it lazily if used again."""
        if self._rt is not None:
            self._rt.close()
            self._rt = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def _bind_placed(stage, bound: Dict[str, Any]):
    """Pre-place the epoch-bound inputs (weights) on the stage's mesh once
    per rebind — they are constant for the whole run, so transferring them
    per microbatch fire would be pure waste. Returns the placed ``bound``
    plus a name->sharding map for per-fire placement of streamed payload
    entries (both empty no-ops when all stages share one mesh)."""
    if stage.in_shardings is None:
        return bound, {}
    import jax

    shard_of = dict(zip(stage.input_names, stage.in_shardings))
    return {n: jax.device_put(v, shard_of[n])
            for n, v in bound.items()}, shard_of


def _place_incoming(input_names, bound: Dict[str, Any],
                    shard_of: Dict[str, Any], payload: Dict[str, Any]):
    """Assemble a stage's positional inputs: pre-placed bound values as-is,
    streamed payload entries transferred onto the stage mesh when stages own
    distinct meshes. Shared by the forward-only and training pipelines."""
    import jax

    return [bound[n] if n in bound else
            (jax.device_put(payload[n], shard_of[n]) if n in shard_of
             else payload[n])
            for n in input_names]


def _stage_binding(stage):
    """Persistent bound-input state for one stage actor: a ``bound`` dict
    the closures read at fire time and an ``on_epoch`` hook that (re)binds
    the values the driver sent in ``ctx`` — placed on the stage's mesh in
    the worker that OWNS the stage, so weights live device-resident where
    they are used and never round-trip through the driver between epochs."""
    bound: Dict[str, Any] = {}
    shard_of = ({} if stage.in_shardings is None
                else dict(zip(stage.input_names, stage.in_shardings)))

    def on_epoch(raw):
        if not raw:
            return
        import jax

        for n, v in raw.items():
            bound[n] = jax.device_put(v, shard_of[n]) if n in shard_of else v
    return bound, shard_of, on_epoch


def _payload_source_spec(name: str, max_fires: int) -> ActorSpec:
    """The streaming source actor: emits one pre-split payload dict per
    version. The payload list is per-epoch state, delivered via ``ctx``."""
    cell: Dict[str, Any] = {"payloads": []}

    def on_epoch(v):
        if v is not None:
            cell["payloads"] = list(v)

    return ActorSpec(
        name=name, fn=lambda version: cell["payloads"][version], inputs=(),
        out_regs=2, node=0, thread=0, max_fires=max_fires,
        wants_version=True, on_epoch=on_epoch)


def stage_actor_specs(staged, microbatch_inputs: Sequence[str],
                      num_microbatches: int,
                      regs: Optional[Sequence[int]] = None,
                      fn_wrap: Optional[Callable[[int, Callable], Callable]] = None,
                      ) -> Tuple[List[ActorSpec], str]:
    """Build the persistent actor graph executing ``staged`` over
    microbatches.

    ``staged`` is a :class:`repro.core.lowering.StagedProgram`. The graph is
    built once and re-run per epoch: each run's inputs arrive via ``ctx`` —
    ``ctx["data"]`` is the pre-split microbatch payload list (one dict per
    version, :func:`repro.core.lowering.split_microbatches`), and
    ``ctx[f"stage{s}"]`` the stage's non-streamed graph inputs (weights),
    which the owning worker places on the stage mesh at epoch start.
    ``regs[s]`` is stage s's out-register quota (default: 1F1B,
    ``num_stages - s``). ``fn_wrap(stage_index, fn)`` optionally decorates
    each stage body (benchmarks use it to emulate device latency).

    Stage ``s`` lives at node ``s + 1`` (the data source at node 0), so the
    process runtime gives each stage its own worker.

    Returns ``(specs, final_stage_name)`` — collect the final stage's
    outputs to reassemble the sinks.
    """
    S = staged.num_stages
    if regs is None:
        regs = [max(1, S - s) for s in range(S)]
    regs = _validate_regs(regs, S, num_microbatches)
    mb_names = list(microbatch_inputs)
    for n in mb_names:
        if n not in staged.input_names:
            raise ValueError(f"{n} is not a graph input")

    # which payload entries each stage must forward to later consumers: any
    # tensor needed by a stage after s still travels the chain at s's output
    graph_inputs = set(staged.input_names)
    needed_after: List[set] = [set() for _ in range(S + 1)]
    sink_names = {t.name for t in staged.sinks}
    for s in reversed(range(S)):
        payload_borne = {n for n in staged.stages[s].input_names
                         if n in mb_names or n not in graph_inputs}
        needed_after[s] = needed_after[s + 1] | payload_borne

    specs: List[ActorSpec] = [_payload_source_spec("data", num_microbatches)]

    def make_stage_fn(stage):
        bound, shard_of, on_epoch = _stage_binding(stage)

        def run_stage(payload):
            import jax

            incoming = _place_incoming(stage.input_names, bound, shard_of,
                                       payload)
            outs = stage.fn(*incoming)
            outs = jax.block_until_ready(outs)
            carried = {n: v for n, v in payload.items()
                       if n in needed_after[stage.index + 1] or n in sink_names}
            carried.update(zip(stage.output_names, outs))
            return carried
        return run_stage, on_epoch

    for s, stage in enumerate(staged.stages):
        fn, on_epoch = make_stage_fn(stage)
        if fn_wrap is not None:
            fn = fn_wrap(s, fn)
        specs.append(ActorSpec(
            name=f"stage{s}", fn=fn,
            inputs=("data",) if s == 0 else (f"stage{s-1}",),
            out_regs=regs[s], node=s + 1, thread=0,
            max_fires=num_microbatches, on_epoch=on_epoch))
    return specs, f"stage{S - 1}"


class InferSpecBuilder(_SpecBuilderBase):
    """Picklable builder of the forward-pipeline actor graph."""

    def __init__(self, microbatch_inputs: Sequence[str],
                 num_microbatches: int, regs=None, fn_wrap=None,
                 staged=None, recipe=None):
        super().__init__(staged=staged, recipe=recipe)
        self.microbatch_inputs = list(microbatch_inputs)
        self.num_microbatches = num_microbatches
        self.regs = None if regs is None else list(regs)
        self.fn_wrap = fn_wrap

    def __call__(self):
        return stage_actor_specs(self.staged, self.microbatch_inputs,
                                 self.num_microbatches, regs=self.regs,
                                 fn_wrap=self.fn_wrap)


class ActorPipelineExecutor(_StagedExecutorBase):
    """Run a :class:`StagedProgram` on the actor runtime.

    The actor graph is built once; each :meth:`run` is one epoch over it:
    the pre-split microbatch payloads and the per-stage bound inputs
    (weights) travel in ``ctx``, ``num_microbatches`` chunks stream through
    the stage chain, and the graph sinks are reassembled by concatenating
    per-microbatch results along axis 0. ``last_makespan`` /
    ``last_history`` expose the wall-clock schedule of the most recent run.
    """

    def __init__(self, staged, microbatch_inputs: Sequence[str],
                 num_microbatches: int, regs: Optional[Sequence[int]] = None,
                 fn_wrap: Optional[Callable] = None,
                 runtime: str = "threads", recipe=None):
        super().__init__(staged, microbatch_inputs, num_microbatches, regs,
                         fn_wrap, runtime=runtime, recipe=recipe)
        self.staged = staged

    def _make_builder(self):
        return InferSpecBuilder(self.microbatch_inputs, self.num_microbatches,
                                regs=self.regs, fn_wrap=self.fn_wrap,
                                staged=self.staged, recipe=self.recipe)

    def run(self, inputs: Dict[str, Any], timeout: float = 300.0) -> Tuple:
        check_run_inputs(inputs, self.staged.input_names)
        from repro.core.lowering import reassemble_sinks, split_microbatches

        graph_inputs = set(self.staged.input_names)
        mb = set(self.microbatch_inputs)
        ctx: Dict[str, Any] = {
            "data": split_microbatches(inputs, self.microbatch_inputs,
                                       self.num_microbatches)}
        for stage in self.staged.stages:
            ctx[f"stage{stage.index}"] = {
                n: inputs[n] for n in stage.input_names
                if n in graph_inputs and n not in mb}
        outs = self._run_rt(ctx, None, timeout)
        if len(outs) != self.num_microbatches:
            raise RuntimeError(
                f"collected {len(outs)} microbatch results, expected "
                f"{self.num_microbatches}")
        # the final stage fires in version order in one worker, so ``outs``
        # is already microbatch-ordered
        return reassemble_sinks(self.staged.graph, self.staged.sinks,
                                self.microbatch_inputs, outs)


# ---------------------------------------------------------------------------
# Training pipelines: backward + optimizer actors (the tentpole of PR 2).
#
# One microbatch's journey: data -> f0 -> f1 -> ... -> f{S-1} -> b{S-1} ->
# ... -> b0, with acc{s} summing each stage's per-microbatch gradients
# (OneFlow's `acc` op, via ActorSpec.emit_every) and opt{s} firing exactly
# once per step on the summed gradient. Stage s's forward out register holds
# BOTH the boundary activations for f{s+1} AND the vjp closure (residuals)
# for b{s}; it is recycled only when both have acked — capping that quota at
# R[s] = S - s is all it takes for the 1F1B schedule to emerge.
#
# Stage s's actors (f, b, acc, opt, state) all live at node s+1, one worker
# mailbox — so the stage's params, optimizer state and gradient accumulator
# are node-local closure state, updated in place by the opt actor and never
# shipped between steps. "__"-prefixed payload keys (the vjp closure, the
# grad stream) are same-node contracts: repro.runtime.base.encode_payload
# strips them at node boundaries.
# ---------------------------------------------------------------------------

_VJP_KEY = "__vjp__"
_GRADS_KEY = "__grads__"


def _train_collect_names(tstaged, snapshot: bool = False,
                         dynamic: bool = False) -> List[str]:
    """The collect list shared by the builder and the executor: the
    loss-bearing backward actor first, then every ``opt{s}``, then (with
    snapshotting on) every ``snap{s}`` — the write receipts the driver
    needs before it finalizes a snapshot's MANIFEST — then (with dynamic
    loss scaling) the ``scale`` actor, whose decision the driver mirrors."""
    produced_at = {n: st.index for st in tstaged.stages
                   for n in st.output_names}
    loss_stage = produced_at[tstaged.loss_name]
    param_stages = [st.index for st in tstaged.stages if st.param_names]
    names = [f"b{loss_stage}"] + [f"opt{s}" for s in param_stages]
    if snapshot:
        names += [f"snap{s}" for s in param_stages]
    if dynamic and param_stages:
        names.append("scale")
    return names


def train_stage_actor_specs(tstaged, microbatch_inputs: Sequence[str],
                            num_microbatches: int, lr: float = 1e-2,
                            regs: Optional[Sequence[int]] = None,
                            fn_wrap: Optional[Callable] = None,
                            optimizer=None, snapshot=None,
                            ) -> Tuple[List[ActorSpec], List[str]]:
    """Build the persistent fwd/bwd/opt actor graph for training steps.

    ``tstaged`` is a :class:`repro.core.lowering.TrainStagedProgram`. The
    graph is built once and re-run per step (one epoch each); per-step
    values arrive via ``ctx``:

    * ``ctx["data"]`` — the pre-split microbatch payload list;
    * ``ctx[f"f{s}"]`` — values to (re)bind on stage s: its non-microbatch
      data inputs every step, plus its params on the first step (or after a
      ``load_params``). The owning worker places them on the stage mesh;
      afterwards ``opt{s}`` updates the same bound dict in place, so params
      stay device-resident in the worker across steps.
    * ``ctx[f"opt{s}"]`` — the step index (resolves the lr schedule), as a
      plain int or as ``{"step": int, "load_state": AdamWState-or-None}``
      after a ``load_state`` restore (the restored moments replace the
      worker-resident state before the epoch's first fire);
    * ``ctx[f"snap{s}"]`` — with ``snapshot`` set, ``{"step": int,
      "write": bool}`` controlling this epoch's checkpoint write.

    ``regs[s]`` is forward stage s's out-register quota (default 1F1B,
    ``num_stages - s``); backward/acc/opt actors need no tuning.
    ``fn_wrap(kind, stage_index, fn)`` with kind in ``{"fwd", "bwd"}``
    optionally decorates the stage bodies.

    The optimizer subsystem (paper §3.3 partial-value + §4.3 actors):

    * ``optimizer`` is a :class:`repro.core.lowering.OptimizerSpec` (falls
      back to ``tstaged.optimizer``, then plain SGD at ``lr``).
    * With ``optimizer.grad_clip`` > 0, every ``acc{s}`` emits its
      stage-local squared-norm partials alongside the summed gradients, and
      a ``norm`` actor — OneFlow's P→B boxing expressed as an actor — sums
      the partials in canonical param order and broadcasts the clip scale
      sideways to every ``opt{s}``.
    * With a stateful optimizer (AdamW), a ``state{s}`` source actor emits
      the stage's current optimizer state as a register that ``opt{s}``
      consumes — the second register stream. The state lives in the stage's
      worker across steps (initialized on the first step); the updated copy
      also rides the opt actor's output payload so the driver can mirror it.
    * With ``snapshot`` (a :class:`repro.runtime.snapshot.SnapshotSpec`), a
      ``snap{s}`` actor per parameterized stage consumes ``opt{s}``'s
      output register — the stream already carrying the post-update params
      and fresh optimizer state — and serializes the stage's slice to disk
      from its own mailbox thread (``thread=1``) with its own register
      quota, so checkpoint writes never sit on the schedule's thread. It
      emits a write receipt the driver collects before finalizing the
      snapshot manifest.

    Gradients are accumulated in fp32 regardless of the backward dtype
    (matching the optimizer kernels' fp32 math); the accumulator is reset
    at every epoch start by its ``on_epoch`` hook.

    Returns ``(specs, collect_names)``: ``collect_names[0]`` is the backward
    actor of the loss-producing stage (the per-microbatch loss stream), the
    rest are the ``opt{s}`` actors (each stage's post-clip gradients,
    updated params, and new optimizer state).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.lowering import OptimizerSpec, loss_scale_update
    from repro.optim.adamw import (clip_scale, global_norm_from_partials,
                                   scale_grad, sqnorm_partials)

    S = tstaged.num_stages
    if regs is None:
        regs = [max(1, S - s) for s in range(S)]
    regs = _validate_regs(regs, S, num_microbatches)
    mb_names = list(microbatch_inputs)
    for n in mb_names:
        if n not in tstaged.input_names:
            raise ValueError(f"{n} is not a graph input")

    opt = optimizer if optimizer is not None else (
        tstaged.optimizer if tstaged.optimizer is not None
        else OptimizerSpec.sgd(lr))
    clip = bool(opt.grad_clip)
    mp = opt.mixed_precision           # fp32 masters live in the opt actor
    compute_dtype = opt.compute_dtype  # what fwd/bwd see (None: keep as-is)
    scaling = opt.loss_scaling is not None
    dynamic = opt.dynamic_scaling
    need_norm = clip or dynamic        # dynamic scaling needs the finiteness
    param_order = tstaged.param_names  # check even with clipping off
    param_stages = [st.index for st in tstaged.stages if st.param_names]

    graph_inputs = set(tstaged.input_names)
    loss_name = tstaged.loss_name

    # forward carry: tensors a stage must forward for later stages' use
    needed_after: List[set] = [set() for _ in range(S + 1)]
    for s in reversed(range(S)):
        payload_borne = {n for n in tstaged.stages[s].input_names
                         if n in mb_names or n not in graph_inputs}
        needed_after[s] = needed_after[s + 1] | payload_borne

    # backward carry: which cotangents b{s} must emit to b{s-1}. A boundary
    # activation produced at stage p collects contributions from every
    # consuming stage >= s on the way down and is consumed as b{p}'s seed.
    produced_at = {n: st.index for st in tstaged.stages
                   for n in st.output_names}
    # the loss stream is collected at the backward actor of the stage that
    # produces the loss sink (usually, but not necessarily, the last stage)
    loss_stage = produced_at[loss_name]
    diff_boundary = {n for st in tstaged.stages
                     for n in st.diff_input_names if n not in st.param_names}
    out_cot_names: List[set] = [set() for _ in range(S)]
    for n in diff_boundary:
        consumers = {st.index for st in tstaged.stages
                     if n in st.diff_input_names}
        for s in range(produced_at[n] + 1, S):
            if any(c >= s for c in consumers):
                out_cot_names[s].add(n)

    specs: List[ActorSpec] = [_payload_source_spec("data", num_microbatches)]

    def make_fwd_fn(stage):
        bound, shard_of, base_on_epoch = _stage_binding(stage)
        # mixed precision: driver-sent params are fp32; the worker stashes
        # them for the opt actor (to (re)build its fp32 masters) and binds
        # the compute-dtype copy — the paper's Fig-14 ``cast`` op, applied
        # once per (re)bind at the forward-stage boundary
        raw_cell: Dict[str, Any] = {}
        pset = set(stage.param_names)

        def on_epoch(raw):
            base_on_epoch(raw)
            if not (mp and raw):
                return
            cdt = jnp.dtype(compute_dtype)
            for n in raw:
                if n in pset:
                    raw_cell[n] = bound[n]
                    bound[n] = bound[n].astype(cdt)

        def run_fwd(payload):
            incoming = _place_incoming(stage.input_names, bound, shard_of,
                                       payload)
            outs, vjp = stage.fwd(*incoming)
            outs = jax.block_until_ready(outs)
            carried = {n: v for n, v in payload.items()
                       if n in needed_after[stage.index + 1]}
            carried.update(zip(stage.output_names, outs))
            carried[_VJP_KEY] = vjp
            return carried
        return run_fwd, bound, raw_cell, on_epoch

    def make_bwd_fn(stage):
        # the loss stage's backward seed: 1 normally, the loss scale when
        # scaling is on (driver-sent via ctx so the worker and the driver
        # mirror never disagree on the step's scale)
        seed_cell = {"scale": None}

        def on_epoch(v):
            if v is not None:
                seed_cell["scale"] = float(v["loss_seed"])

        def run_bwd(f_payload, b_payload=None):
            incoming = {} if b_payload is None else b_payload["cots"]
            grads, contrib = {}, {}
            if stage.bwd is not None:
                seeds = stage.output_cotangents(f_payload, incoming,
                                                loss_name,
                                                loss_seed=seed_cell["scale"])
                in_cots = stage.bwd(f_payload[_VJP_KEY], seeds)
                in_cots = jax.block_until_ready(in_cots)
                for n, c in zip(stage.diff_input_names, in_cots):
                    if n in stage.param_names:
                        grads[n] = c
                    else:
                        contrib[n] = c
            out_cots = {}
            for n in out_cot_names[stage.index]:
                c = incoming.get(n)
                if n in contrib:
                    c = contrib[n] if c is None else c + contrib[n]
                out_cots[n] = c
            # the per-stage grad stream rides a same-node private key: only
            # acc{s} (same worker) reads it, so it never crosses to b{s-1}
            out = {"cots": out_cots, _GRADS_KEY: grads}
            if stage.index == loss_stage:
                # reduce to a scalar HERE, on the stage's own mesh: summing
                # driver-side would re-partition the reduction after the
                # tensor crossed a process boundary as a gathered numpy
                # array, changing the f32 rounding vs the threaded path
                out["loss"] = jnp.sum(f_payload[loss_name])
            return out
        return run_bwd, on_epoch

    def make_acc_fn():
        # per-microbatch gradients accumulate in fp32 (the optimizer kernels'
        # math dtype) no matter what dtype the backward emits (e.g. bf16);
        # the accumulator is epoch-local state, reset by on_epoch. With loss
        # scaling on, the driver sends ``1/scale`` and the accumulator
        # unscales ONCE on its final fire — before the squared-norm partials,
        # so the norm (and the finiteness check behind dynamic scaling) is of
        # the true gradients.
        state: Dict[str, Any] = {}
        meta = {"fires": 0, "inv": None}

        def on_epoch(v):
            state.clear()
            meta["fires"] = 0
            meta["inv"] = None if v is None else v.get("inv_scale")

        def run_acc(b_payload):
            meta["fires"] += 1
            for n, g in b_payload[_GRADS_KEY].items():
                g32 = g.astype(jnp.float32)
                state[n] = state[n] + g32 if n in state else g32
            final = meta["fires"] == num_microbatches
            if final and meta["inv"] is not None:
                for n in state:
                    state[n] = scale_grad(state[n], meta["inv"])
            out = {_GRADS_KEY: dict(state)}
            if need_norm and final:
                # the stage-local P contribution to the global grad norm
                out["sqnorms"] = sqnorm_partials(state)
            return out
        return run_acc, on_epoch

    def make_opt_fn(stage, bound, raw_cell, state_cell):
        pnames = stage.param_names
        meta = {"step": 0}

        def on_epoch(v):
            if v is None:
                return
            if isinstance(v, dict):
                meta["step"] = int(v["step"])
                if "load_state" in v:
                    # restore seam: replace the worker-resident optimizer
                    # state before this epoch's state{s} fire emits it
                    state_cell["state"] = v["load_state"]
            else:
                meta["step"] = int(v)

        def refresh_masters():
            # (re)build the fp32 masters from the fp32 params the driver
            # just sent (first step, load_params, or a snapshot restore) —
            # sharded flat for ZeRO, dense fp32 otherwise. The register
            # stream the opt actor owns from here on.
            raw = {n: raw_cell[n] for n in pnames}
            if opt.zero:
                masters = opt.shard_masters(raw)
            else:
                masters = {n: v.astype(jnp.float32) for n, v in raw.items()}
            state_cell["masters"] = masters
            state_cell["shapes"] = {n: tuple(v.shape) for n, v in raw.items()}
            raw_cell.clear()

        def run_opt(acc_payload, *rest):
            idx = 0
            norm_payload = scale_payload = None
            state = None
            if need_norm:
                norm_payload = rest[idx]
                idx += 1
            if dynamic:
                scale_payload = rest[idx]
                idx += 1
            if opt.stateful:
                state = rest[idx]["state"]
                idx += 1
            if mp and raw_cell:
                refresh_masters()
            if scale_payload is not None and scale_payload["skip"]:
                # non-finite grads under dynamic scaling: no update, no step
                # advance — the register stream (masters/moments/bound
                # params) is left exactly as it was
                out = {"skipped": True,
                       "scale": scale_payload["scale"],
                       "next_scale": scale_payload["next_scale"],
                       "good_steps": scale_payload["good_steps"]}
                if norm_payload is not None:
                    out["norm"] = norm_payload["norm"]
                return out
            grads = acc_payload[_GRADS_KEY]
            if norm_payload is not None:
                grads = {n: scale_grad(grads[n], norm_payload["scale"])
                         for n in pnames}
            else:
                grads = {n: grads[n] for n in pnames}
            if mp:
                params = state_cell["masters"]
            else:
                params = {n: bound[n] for n in pnames}
            if opt.stateful and state is None:
                # first step in this worker: fresh (zeroed) state — the
                # same values the driver-side mirror starts from
                state = opt.init_state(params)
            lr_now = opt.lr_at(meta["step"])
            meta["step"] += 1
            new_params, new_state = opt.update(params, grads, state, lr_now)
            new_params = jax.block_until_ready(new_params)
            # the stage's persistent state advances IN the worker: the next
            # epoch's forward reads the updated bound params, state{s} emits
            # the updated optimizer state
            if mp:
                shapes = state_cell["shapes"]
                state_cell["masters"] = new_params
                if opt.zero:
                    # gather for next step's forward at compute width (the
                    # Fig-14 cast BEFORE the gather: half the wire bytes)
                    bound.update(opt.gather_params(
                        new_params, dtype=compute_dtype, shapes=shapes))
                    full = opt.gather_params(new_params, shapes=shapes)
                else:
                    bound.update({n: v.astype(jnp.dtype(compute_dtype))
                                  for n, v in new_params.items()})
                    full = new_params
            else:
                bound.update(new_params)
                full = new_params
            if opt.stateful:
                state_cell["state"] = new_state
            # the driver mirror always sees full fp32 params; snap{s} (same
            # node) additionally sees the raw shards via a private key
            out = {"params": full, "grads": grads}
            if opt.stateful:
                out["state"] = new_state
            if opt.zero:
                out["__zero__"] = {"masters": new_params, "state": new_state,
                                   "shapes": state_cell["shapes"],
                                   "dp": opt.zero_dp}
            if scale_payload is not None:
                out["scale"] = scale_payload["scale"]
                out["next_scale"] = scale_payload["next_scale"]
                out["good_steps"] = scale_payload["good_steps"]
            if norm_payload is not None:
                out["norm"] = norm_payload["norm"]
            return out
        return run_opt, on_epoch

    def make_snap_fn(stage):
        # the snapshot actor's per-epoch control cell: which step this
        # epoch's write belongs to, and whether to write at all
        cell = {"step": 0, "write": False}

        def on_epoch(v):
            if v is not None:
                cell["step"] = int(v["step"])
                cell["write"] = bool(v["write"])

        def run_snap(opt_payload):
            from repro.runtime.snapshot import write_stage_snapshot

            write = cell["write"] and not opt_payload.get("skipped")
            if write:
                zero_meta = opt_payload.get("__zero__")
                if zero_meta is not None:
                    # ZeRO: persist the flat master/moment *shards* (the
                    # "__zero__" key is a same-node contract — snap{s} lives
                    # on the opt actor's node, so it sees the raw stream);
                    # load_snapshot gathers them partition-agnostically
                    write_stage_snapshot(
                        snapshot.dir, cell["step"], stage.index,
                        dict(zero_meta["masters"]),
                        opt_state=zero_meta["state"],
                        zero={"dp": zero_meta["dp"],
                              "shapes": {n: list(s) for n, s in
                                         zero_meta["shapes"].items()}})
                else:
                    write_stage_snapshot(
                        snapshot.dir, cell["step"], stage.index,
                        {n: opt_payload["params"][n]
                         for n in stage.param_names},
                        opt_state=opt_payload.get("state"))
            return {"stage": stage.index, "step": cell["step"],
                    "written": write}
        return run_snap, on_epoch

    collect = _train_collect_names(tstaged, snapshot=snapshot is not None,
                                   dynamic=dynamic)
    for s, stage in enumerate(tstaged.stages):
        fwd_fn, bound, raw_cell, fwd_on_epoch = make_fwd_fn(stage)
        bwd_fn, bwd_on_epoch = make_bwd_fn(stage)
        if fn_wrap is not None:
            fwd_fn = fn_wrap("fwd", s, fwd_fn)
            bwd_fn = fn_wrap("bwd", s, bwd_fn)
        specs.append(ActorSpec(
            name=f"f{s}", fn=fwd_fn,
            inputs=("data",) if s == 0 else (f"f{s-1}",),
            out_regs=regs[s], node=s + 1, thread=0,
            max_fires=num_microbatches, on_epoch=fwd_on_epoch))
        specs.append(ActorSpec(
            name=f"b{s}", fn=bwd_fn,
            inputs=(f"f{s}",) if s == S - 1 else (f"f{s}", f"b{s+1}"),
            out_regs=2, node=s + 1, thread=0,
            max_fires=num_microbatches,
            on_epoch=bwd_on_epoch if (scaling and s == loss_stage)
            else None))
        if stage.param_names:
            acc_fn, acc_on_epoch = make_acc_fn()
            specs.append(ActorSpec(
                name=f"acc{s}", fn=acc_fn, inputs=(f"b{s}",),
                out_regs=1, node=s + 1, thread=0,
                max_fires=num_microbatches, emit_every=num_microbatches,
                on_epoch=acc_on_epoch))
            opt_inputs = (f"acc{s}",)
            if need_norm:
                opt_inputs += ("norm",)
            if dynamic:
                opt_inputs += ("scale",)
            state_cell: Dict[str, Any] = {"state": None, "masters": None,
                                          "shapes": None}
            if opt.stateful:
                # the optimizer-state register stream: a source actor emits
                # the worker-resident AdamWState (flat ZeroState shards when
                # zero=True); opt{s} consumes it next to the summed
                # gradients and the broadcast clip scale
                specs.append(ActorSpec(
                    name=f"state{s}",
                    fn=lambda _c=state_cell: {"state": _c["state"]},
                    inputs=(), out_regs=1, node=s + 1, thread=0,
                    max_fires=1))
                opt_inputs += (f"state{s}",)
            opt_fn, opt_on_epoch = make_opt_fn(stage, bound, raw_cell,
                                               state_cell)
            specs.append(ActorSpec(
                name=f"opt{s}", fn=opt_fn,
                inputs=opt_inputs, out_regs=1, node=s + 1, thread=0,
                max_fires=1, on_epoch=opt_on_epoch))
            if snapshot is not None:
                # async checkpointing as one more register-stream consumer:
                # snap{s} subscribes to opt{s}'s output (post-update params
                # + fresh optimizer state) on the stage node's thread 1 —
                # its own mailbox, OS thread and register quota, so
                # serialization never blocks the schedule on thread 0
                snap_fn, snap_on_epoch = make_snap_fn(stage)
                specs.append(ActorSpec(
                    name=f"snap{s}", fn=snap_fn, inputs=(f"opt{s}",),
                    out_regs=1, node=s + 1, thread=1,
                    max_fires=1, on_epoch=snap_on_epoch))

    if need_norm and param_stages:
        # cross-stage *sideways* communication on the actor protocol: sum the
        # per-stage squared-norm partials (P→B boxing as an actor) and
        # broadcast the clip scale to every opt{s} (1.0 when clipping is off
        # and the norm only feeds the dynamic-scaling finiteness check)
        def run_norm(*acc_payloads):
            partials = {}
            for pl in acc_payloads:
                partials.update(pl["sqnorms"])
            norm = global_norm_from_partials(partials, param_order)
            return {"norm": norm, "scale": clip_scale(norm, opt.grad_clip)}

        specs.append(ActorSpec(
            name="norm", fn=run_norm,
            inputs=tuple(f"acc{s}" for s in param_stages),
            out_regs=1, node=0, thread=0, max_fires=1))

    if dynamic and param_stages:
        # dynamic loss scaling rides the norm actor's sideways P→B edge: one
        # more actor inspects the true-gradient norm for finiteness and
        # broadcasts the skip/backoff/growth decision to every opt{s}. The
        # driver re-seeds the cell each step via ctx["scale"], so kills and
        # restores never fork the scale trajectory.
        sc_cell = {"scale": float(opt.initial_scale()), "good": 0}

        def sc_on_epoch(v):
            if v is not None:
                sc_cell["scale"] = float(v["scale"])
                sc_cell["good"] = int(v["good_steps"])

        def run_scale(norm_payload):
            finite = bool(np.isfinite(np.float32(norm_payload["norm"])))
            skip, nxt, good = loss_scale_update(
                opt.precision, sc_cell["scale"], sc_cell["good"], finite)
            out = {"skip": skip, "scale": sc_cell["scale"],
                   "next_scale": nxt, "good_steps": good}
            sc_cell["scale"], sc_cell["good"] = nxt, good
            return out

        specs.append(ActorSpec(
            name="scale", fn=run_scale, inputs=("norm",),
            out_regs=1, node=0, thread=0, max_fires=1,
            on_epoch=sc_on_epoch))

    return specs, collect


class TrainSpecBuilder(_SpecBuilderBase):
    """Picklable builder of the fwd/bwd/opt training actor graph."""

    def __init__(self, microbatch_inputs: Sequence[str],
                 num_microbatches: int, lr: float = 1e-2, regs=None,
                 fn_wrap=None, optimizer=None, staged=None, recipe=None,
                 snapshot=None):
        super().__init__(staged=staged, recipe=recipe)
        self.microbatch_inputs = list(microbatch_inputs)
        self.num_microbatches = num_microbatches
        self.lr = lr
        self.regs = None if regs is None else list(regs)
        self.fn_wrap = fn_wrap
        self.optimizer = optimizer
        self.snapshot = snapshot      # SnapshotSpec (plain data — picklable)

    def __call__(self):
        return train_stage_actor_specs(self.staged, self.microbatch_inputs,
                                       self.num_microbatches, lr=self.lr,
                                       regs=self.regs, fn_wrap=self.fn_wrap,
                                       optimizer=self.optimizer,
                                       snapshot=self.snapshot)


class TrainPipelineExecutor(_StagedExecutorBase):
    """Run a :class:`TrainStagedProgram` as a 1F1B training pipeline.

    The fwd/bwd/opt actor graph is built once; each :meth:`step` is one
    epoch over it. Per-stage persistent state — placed params, the AdamW
    state, the fp32 gradient accumulator — lives in the stage's actor
    closures, resident in whichever worker owns the stage (its OS thread
    under ``runtime="threads"``, its worker process under
    ``runtime="processes"``): the opt actor updates the stage's bound
    params and optimizer state in place, so nothing round-trips through the
    driver between steps. The driver keeps a mirror (``params``,
    ``opt_states``) refreshed from the opt actors' collected outputs, and
    returns ``(loss, grads, params)`` bit-identical to the monolithic
    reference (:func:`repro.train.steps.make_graph_train_step` with the
    same :class:`repro.core.lowering.OptimizerSpec`; the objective is the
    *sum* of the loss tensor over the batch, ``grads`` are post-clip when
    global-norm clipping is on).

    ``opt_state`` merges the per-stage states; ``last_grad_norm`` is the
    global gradient norm the ``norm`` actor computed (None when clipping is
    off). Instrumentation mirrors :class:`ActorPipelineExecutor`:
    ``last_makespan`` (wall-clock seconds), ``last_history`` (per-actor
    action intervals), ``last_peak_regs`` (per-actor peak out-registers in
    use — ``f{s}`` entries are the in-flight activation counts the 1F1B
    quota bounds), ``last_edge_bytes`` (per-edge payload traffic).
    """

    def __init__(self, tstaged, params: Dict[str, Any],
                 microbatch_inputs: Sequence[str], num_microbatches: int,
                 lr: float = 1e-2, regs: Optional[Sequence[int]] = None,
                 fn_wrap: Optional[Callable] = None, optimizer=None,
                 runtime: str = "threads", recipe=None,
                 snapshot_dir: Optional[str] = None, snapshot_every: int = 1,
                 faults=None):
        from repro.core.lowering import OptimizerSpec

        super().__init__(tstaged, microbatch_inputs, num_microbatches, regs,
                         fn_wrap, runtime=runtime, recipe=recipe,
                         faults=faults)
        self.tstaged = tstaged
        self.lr = lr
        self.optimizer = optimizer if optimizer is not None else (
            tstaged.optimizer if tstaged.optimizer is not None
            else OptimizerSpec.sgd(lr))
        if snapshot_every < 1:
            raise ValueError(
                f"snapshot_every must be >= 1, got {snapshot_every}")
        self._snapshot = None
        if snapshot_dir is not None:
            from repro.runtime.snapshot import SnapshotSpec
            self._snapshot = SnapshotSpec(str(snapshot_dir))
        self.snapshot_every = snapshot_every
        self._state_dirty = False
        self.params: Dict[str, Any] = {}
        self.load_params(params)
        # driver-side mirror of the per-stage optimizer state (None entries
        # for SGD; flat ZeroState shards when zero=True); the workers
        # initialize their own identical (zeroed) copy on the first step and
        # send each update back on the opt payload
        self.opt_states: Dict[int, Any] = {
            st.index: self._fresh_state(st)
            for st in tstaged.stages if st.param_names}
        self.step_count = 0
        self.last_grad_norm = None
        # loss-scaling mirror: the driver owns the scale authority — it
        # seeds the backward pass and the unscale factor via ctx every step
        self._scaling = self.optimizer.loss_scaling is not None
        self.loss_scale = (self.optimizer.initial_scale()
                           if self._scaling else None)
        self.scale_good_steps = 0
        self.last_skipped = False
        self.last_scale = None      # the scale the last step ran under
        self._loss_stage = next(
            st.index for st in tstaged.stages
            if tstaged.loss_name in st.output_names)

    def _fresh_state(self, st):
        """A zeroed optimizer state for stage ``st`` — sharded flat when the
        optimizer runs ZeRO, matching what the stage's worker builds."""
        p = {n: self.params[n] for n in st.param_names}
        if self.optimizer.zero:
            p = self.optimizer.shard_masters(p)
        return self.optimizer.init_state(p)

    def _make_builder(self):
        return TrainSpecBuilder(self.microbatch_inputs, self.num_microbatches,
                                lr=self.lr, regs=self.regs,
                                fn_wrap=self.fn_wrap,
                                optimizer=self.optimizer,
                                staged=self.tstaged, recipe=self.recipe,
                                snapshot=self._snapshot)

    def load_params(self, params: Dict[str, Any]) -> None:
        """Replace the executor-owned params (e.g. a checkpoint restore).

        The new values ride the next step's ``ctx`` into each stage's
        worker, which places them on its mesh; afterwards the opt actors
        keep them device-resident. Optimizer state is untouched — reset
        ``opt_states`` separately if the new params are unrelated to the
        old trajectory.
        """
        missing = [n for n in self.tstaged.param_names if n not in params]
        if missing:
            raise ValueError(f"missing params: {missing}")
        self.params = {n: params[n] for n in self.tstaged.param_names}
        self._params_dirty = True

    def load_state(self, params: Optional[Dict[str, Any]] = None,
                   opt_state=None, step: Optional[int] = None) -> None:
        """Restore a full training state (the kill-and-resume seam).

        Extends :meth:`load_params` with the two pieces a restart must not
        lose: ``opt_state`` — a *merged* :class:`repro.optim.adamw
        .AdamWState` over all params (e.g. from
        :func:`repro.runtime.snapshot.load_snapshot`), split per stage by
        THIS executor's partition, so a snapshot restores onto a different
        stage cut — and ``step``, the optimizer-step counter the lr
        schedule is indexed by. The restored moments ride the next step's
        ``ctx`` into each stage's worker, replacing the worker-resident
        state before its ``state{s}`` actor fires.
        """
        if params is not None:
            self.load_params(params)
        if opt_state is not None:
            if not self.optimizer.stateful:
                raise ValueError(
                    "opt_state given but the optimizer is stateless "
                    f"({self.optimizer.kind})")
            self.opt_states = self.optimizer.split_state(
                opt_state, {st.index: st.param_names
                            for st in self.tstaged.stages if st.param_names})
            self._state_dirty = True
        if step is not None:
            self.step_count = int(step)

    @property
    def peak_inflight_activations(self) -> int:
        """Peak forward registers in use across stages in the last step —
        the in-flight microbatch count the quota back-pressures. Zero
        before the first step (or for a zero-stage program)."""
        return max((self.last_peak_regs.get(f"f{s}", 0)
                    for s in range(self.tstaged.num_stages)), default=0)

    @property
    def opt_state(self):
        """The per-stage optimizer states merged into one
        :class:`repro.optim.adamw.AdamWState` over all params (None for a
        stateless optimizer)."""
        return self.optimizer.merge_states(
            [self.opt_states[s] for s in sorted(self.opt_states)])

    def step(self, data_inputs: Dict[str, Any], timeout: float = 300.0):
        """Run one training step over the current params.

        ``data_inputs`` maps non-param graph inputs to values (the
        microbatched ones are split along axis 0). Updates ``self.params``,
        ``self.opt_states`` and the step counter in place and returns
        ``(loss, grads, params)``.
        """
        import jax.numpy as jnp
        import numpy as np

        from repro.core.lowering import split_microbatches

        check_run_inputs(
            data_inputs,
            [n for n in self.tstaged.input_names if n not in self.params],
            owned=self.tstaged.param_names)
        graph_inputs = set(self.tstaged.input_names)
        mb = set(self.microbatch_inputs)
        ctx: Dict[str, Any] = {
            "data": split_microbatches(data_inputs, self.microbatch_inputs,
                                       self.num_microbatches)}
        snap_step = self.step_count + 1   # the state after THIS step lands
        write = (self._snapshot is not None
                 and snap_step % self.snapshot_every == 0)
        if self._scaling:
            # seed the loss stage's backward with the scale, the acc actors
            # with 1/scale (exact for power-of-two scales), and re-anchor
            # the scale actor's cell at the driver's authoritative mirror
            inv = np.float32(np.float32(1.0) / np.float32(self.loss_scale))
            self.last_scale = self.loss_scale
            ctx[f"b{self._loss_stage}"] = {"loss_seed": float(self.loss_scale)}
            if self.optimizer.dynamic_scaling:
                ctx["scale"] = {"scale": self.loss_scale,
                                "good_steps": self.scale_good_steps}
        for st in self.tstaged.stages:
            bound = {n: data_inputs[n] for n in st.input_names
                     if n in graph_inputs and n not in mb
                     and n not in self.params}
            if self._params_dirty:
                bound.update({n: self.params[n] for n in st.param_names})
            ctx[f"f{st.index}"] = bound
            if st.param_names:
                if self._scaling:
                    ctx[f"acc{st.index}"] = {"inv_scale": inv}
                if self._state_dirty:
                    ctx[f"opt{st.index}"] = {
                        "step": self.step_count,
                        "load_state": self.opt_states[st.index]}
                else:
                    ctx[f"opt{st.index}"] = self.step_count
                if self._snapshot is not None:
                    ctx[f"snap{st.index}"] = {"step": snap_step,
                                              "write": write}
        outs = self._run_rt(ctx, None, timeout)
        self._params_dirty = False
        self._state_dirty = False

        collect = _train_collect_names(
            self.tstaged, snapshot=self._snapshot is not None,
            dynamic=self.optimizer.dynamic_scaling)
        # the loss-bearing backward actor fires in version order in one
        # worker, so the collected loss stream is microbatch-ordered
        loss_payloads = outs[collect[0]]
        if len(loss_payloads) != self.num_microbatches:
            raise RuntimeError(
                f"collected {len(loss_payloads)} loss chunks, expected "
                f"{self.num_microbatches}")
        loss = None
        for pl in loss_payloads:
            ls = jnp.sum(pl["loss"])
            loss = ls if loss is None else loss + ls

        grads: Dict[str, Any] = {}
        norm = None
        skipped = False
        for name in collect[1:]:
            if not name.startswith("opt"):
                continue
            (opt_out,) = outs[name]        # optimizer fired exactly once
            s = int(name[len("opt"):])
            if "norm" in opt_out:
                norm = opt_out["norm"]
            if opt_out.get("skipped"):
                skipped = True
                continue
            grads.update(opt_out["grads"])
            self.params.update(opt_out["params"])
            if "state" in opt_out:
                self.opt_states[s] = opt_out["state"]
        self.last_grad_norm = norm
        if self.optimizer.dynamic_scaling:
            (sc,) = outs["scale"]
            skipped = bool(sc["skip"])
            self.loss_scale = float(sc["next_scale"])
            self.scale_good_steps = int(sc["good_steps"])
        self.last_skipped = skipped
        if write and not skipped:
            self._finalize_snapshot(outs, snap_step)
        if not skipped:
            self.step_count += 1
        return loss, grads, dict(self.params)

    def _finalize_snapshot(self, outs, snap_step: int) -> None:
        """Write the snapshot MANIFEST — only after every stage's snap actor
        delivered a write receipt for this step. The MANIFEST is the
        completeness marker: a step killed mid-write leaves stage dirs
        without one, and restore ignores them."""
        from repro.runtime.snapshot import write_manifest

        receipts = []
        for st in self.tstaged.stages:
            if not st.param_names:
                continue
            (r,) = outs[f"snap{st.index}"]
            if not r["written"] or int(r["step"]) != snap_step:
                raise RuntimeError(
                    f"snapshot receipt mismatch from stage {st.index}: {r} "
                    f"(expected written step {snap_step})")
            receipts.append(int(r["stage"]))
        meta = {"param_names": list(self.tstaged.param_names),
                "stateful": self.optimizer.stateful,
                "optimizer": self.optimizer.kind,
                "num_stages": self.tstaged.num_stages,
                "zero": bool(self.optimizer.zero)}
        if self._scaling:
            # the scale to RESUME with (already advanced past this step)
            meta["loss_scale"] = float(self.loss_scale)
            meta["scale_good_steps"] = int(self.scale_good_steps)
        write_manifest(self._snapshot.dir, snap_step, receipts, meta=meta)

    def opt_state_bytes(self) -> Dict[int, int]:
        """Per-stage bytes of worker-resident optimizer-held fp32 state.

        With a mixed-precision optimizer this is masters + moments (3x the
        fp32 param bytes dense, 3x/DP per device under ZeRO); for plain
        AdamW it is the two moment tensors (the params themselves are the
        model, not optimizer state). The DP-fold memory saving the ZeRO
        stream buys is visible here without a profiler."""
        import numpy as np

        out: Dict[int, int] = {}
        zero_dp = self.optimizer.zero_dp if self.optimizer.zero else 1
        for st in self.tstaged.stages:
            if not st.param_names:
                continue
            total = 0
            state = self.opt_states.get(st.index)
            if state is not None:
                for tree in (state.mu, state.nu):
                    total += sum(int(np.asarray(v).nbytes)
                                 for v in tree.values())
            if self.optimizer.mixed_precision:
                # fp32 masters, flat-sharded under ZeRO
                for n in st.param_names:
                    nelem = int(np.asarray(self.params[n]).size)
                    chunk = -(-nelem // zero_dp) * zero_dp
                    total += chunk * 4
            out[st.index] = total // zero_dp   # per-device share
        return out


# ---------------------------------------------------------------------------
# Serving pipelines: continuous-batching decode on the actor protocol.
#
# Stage = contiguous model shard (repro.core.lowering.lower_serve_stages);
# microbatch = request group. Each round streams one work item per live group
# through the stage chain: a DecodeWork advances every slot of the group by
# one token, a PrefillWork runs one freshly admitted request's prompt and
# scatters its caches into the group cache. The stage's KV/SSM caches never
# ride the payload — they are persistent stage-local state in the stage
# actor's closure (the same pattern as the AdamW state stream in training),
# resident in whichever worker owns the stage, so the only tensors crossing
# stages are the (B, 1, d) hidden and the final logits. Overlap across
# groups emerges from the stage out-register quotas alone (§4.3): while
# stage 1 decodes group 0, stage 0 already decodes group 1.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PrefillWork:
    """Admit one request: run its prompt, build its slot caches.

    ``tokens`` is (1, prompt_len) int32 (batch-replicated); ``last_index``
    is the prompt's final position — the first generated token's logits are
    gathered there through the decode head. Under ``cache="paged"``,
    ``sid`` is the request's slot id in the page pool and ``row`` its
    *write* page-table row (shared-prefix entries masked to ``-1``)."""

    group: int
    slot: int
    tokens: Any
    last_index: int
    sid: int = -1
    row: Any = None


@dataclasses.dataclass
class DecodeWork:
    """Advance every slot of ``group`` by one token. ``tok``/``pos`` are
    (group_size,) int32; retired slots are parked (see ServeSession).
    Under ``cache="paged"``, ``sids``/``rows`` carry each slot's pool id
    and page-table row (``-1`` rows for parked or mid-chunk slots)."""

    group: int
    tok: Any
    pos: Any
    sids: Any = None
    rows: Any = None


@dataclasses.dataclass
class PrefillChunkWork:
    """One bounded chunked-prefill step for slot ``(group, slot)``
    (``cache="paged"`` only): the stage's scan-of-decode chunk program over
    ``toks`` (chunk_len, group_size), slot ``b`` visiting positions
    ``pos0[b] + t * adv[b]``. Non-owner columns are parked no-ops
    (``adv == 0``, table row ``-1``) so the group program keeps one fixed
    shape per chunk length. ``sids_in`` gates the state-row gather (``-1``
    on the first chunk: recurrent state starts from exact zeros),
    ``sids_out`` the state-row scatter. ``final`` marks the chunk whose
    last-position logits produce the request's first token."""

    group: int
    slot: int
    toks: Any
    pos0: Any
    adv: Any
    rows: Any
    sids_in: Any
    sids_out: Any
    final: bool


def _work_input(work):
    """The first stage's input tensor for a work item: prompt ids for a
    prefill, the chunk token matrix for a chunk, last tokens for a decode."""
    if isinstance(work, PrefillWork):
        return work.tokens
    if isinstance(work, PrefillChunkWork):
        return work.toks
    return work.tok


class DenseStageCache:
    """The dense per-group cache dict behind a stage-cache interface: one
    ``(group_size, cache_len, ...)`` block per slot group, allocated lazily
    the first time the group reaches the stage. This is the PR-5 semantics,
    bit for bit — the reference the paged cache is checked against."""

    def __init__(self, stage, group_size: int):
        self.stage = stage
        self.group_size = group_size
        self.caches: Dict[int, Any] = {}

    def _ensure(self, group: int) -> None:
        if group not in self.caches:
            import jax.numpy as jnp

            tok = jnp.zeros((self.group_size,), jnp.int32)
            self.caches[group] = self.stage.init_caches(tok)

    def write_prefill(self, work, slot_caches) -> None:
        from jax.profiler import TraceAnnotation

        self._ensure(work.group)
        with TraceAnnotation("stage.scatter", kind="prefill"):
            self.caches[work.group] = self.stage.write_slot(
                self.caches[work.group], slot_caches, work.slot)

    def run_decode(self, work, xin):
        import jax
        from jax.profiler import TraceAnnotation

        from repro.core.lowering import split_moe_counts

        self._ensure(work.group)
        with TraceAnnotation("stage.program", kind="decode"):
            xout, new_caches = split_moe_counts(self.stage, self.stage.decode(
                self.stage.params, self.caches[work.group], xin, work.pos))
        with TraceAnnotation("stage.sync", kind="decode"):
            xout = jax.block_until_ready(xout)
        self.caches[work.group] = new_caches
        return xout

    def run_chunk(self, work, xin):
        raise RuntimeError(
            "chunked prefill (PrefillChunkWork) requires cache='paged'; the "
            "dense cache admits whole prompts only")


def make_stage_cache(stage, group_size: int, cache_len: int, spec=None):
    """One stage's serving cache: dense per-group blocks, or the paged
    slab pool when a :class:`repro.serve.paged_cache.PagedCacheSpec` is
    given."""
    if spec is None:
        return DenseStageCache(stage, group_size)
    from repro.serve.paged_cache import PagedStageCache

    return PagedStageCache(stage, group_size, cache_len, spec)


def serve_stage_apply(stage, cache, work, xin):
    """Run one work item through one serve stage, updating the stage's
    persistent cache in place. ``cache`` is a :class:`DenseStageCache` /
    ``PagedStageCache`` (or the bare dense per-group dict, accepted for
    compatibility). Returns the stage's output tensor (the hidden
    mid-pipeline, the logits on the last stage). Shared by the actor
    executor and the monolithic serve engine so their math is identical."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.core.lowering import split_moe_counts

    if isinstance(cache, dict):
        dense = DenseStageCache(stage, 0)
        dense.caches = cache
        dense._ensure = lambda group: None      # caller pre-allocated
        cache = dense
    if isinstance(work, PrefillWork):
        li = jnp.full((work.tokens.shape[0],), work.last_index, jnp.int32)
        with TraceAnnotation("stage.program", kind="prefill"):
            xout, slot_caches = split_moe_counts(
                stage, stage.prefill(stage.params, xin, li))
        with TraceAnnotation("stage.sync", kind="prefill"):
            xout = jax.block_until_ready(xout)
        cache.write_prefill(work, slot_caches)
        return xout
    if isinstance(work, PrefillChunkWork):
        return cache.run_chunk(work, xin)
    return cache.run_decode(work, xin)


class _ServeEngineBase:
    """Shared state of the inline serving engine: one persistent stage
    cache per stage (dense per-group blocks or the paged slab pool — the
    register stream that outlives every round), the optional sampler
    stream, and round instrumentation."""

    def _init_serve_state(self, sstaged, cache_spec=None,
                          sampling=None) -> None:
        self.sstaged = sstaged
        self.cache_spec = cache_spec
        self.sampling = sampling
        self.stage_caches = [
            make_stage_cache(stage, sstaged.group_size, sstaged.cache_len,
                             cache_spec)
            for stage in sstaged.stages]
        self.sampler = None
        if sampling is not None:
            from repro.serve.sampler import SamplerStream

            self.sampler = SamplerStream(sampling, sstaged.cfg.vocab_size)
        self.rounds = 0


def _finish_round_item(sampler, work, logits):
    """Shape one round result. Without a sampler the result is the raw
    logits (the PR-5 protocol, untouched). With one, it is
    ``{"logits", "tokens"}`` — the sampler key advances once per
    token-producing item (never for a non-final chunk), in work order, so
    every backend/runtime consumes the key stream identically."""
    if sampler is None:
        return logits
    if isinstance(work, PrefillChunkWork):
        if not work.final:
            return {"logits": logits, "tokens": None}
        return {"logits": logits, "tokens": sampler.sample(logits[-1])}
    return {"logits": logits, "tokens": sampler.sample(logits)}


class InlineServeEngine(_ServeEngineBase):
    """``backend="monolithic"`` serving: the same round protocol as the
    actor executor, run inline (no actors) over a whole-stack
    ``lower_serve_stages(num_stages=1)`` program — the reference the
    pipelined engine is checked against, token for token."""

    def __init__(self, sstaged, cache_spec=None, sampling=None):
        self._init_serve_state(sstaged, cache_spec, sampling)
        self.last_makespan: Optional[float] = None

    def run_round(self, work: Sequence, timeout: float = 300.0) -> List:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("runtime.round"):
            t0 = time.perf_counter()
            results = []
            for w in work:
                xin = _work_input(w)
                for cache in self.stage_caches:
                    xin = serve_stage_apply(cache.stage, cache, w, xin)
                results.append(_finish_round_item(self.sampler, w, xin))
            self.last_makespan = time.perf_counter() - t0
        self.rounds += 1
        return results


def serve_stage_actor_specs(sstaged, regs: Optional[Sequence[int]] = None,
                            fn_wrap: Optional[Callable] = None,
                            cache_spec=None, sampling=None,
                            ) -> Tuple[List[ActorSpec], str]:
    """Build the persistent serve actor graph: an ``admit`` source emitting
    the round's work items (delivered via ``ctx["admit"]``, with ``fires``
    set to the round's work count) and one ``stage{s}`` actor per model
    shard at node ``s + 1``, each owning its per-group KV/SSM caches as
    closure state — allocated lazily the first time a group reaches the
    stage, resident in the owning worker across rounds.

    Returns ``(specs, final_stage_name)``."""
    S = sstaged.num_stages
    if regs is None:
        regs = [max(1, S - s) for s in range(S)]
    regs = _validate_regs(regs, S)

    cell: Dict[str, Any] = {"work": []}

    def on_epoch(v):
        if v is not None:
            cell["work"] = list(v)

    specs: List[ActorSpec] = [ActorSpec(
        name="admit", fn=lambda version: {"work": cell["work"][version]},
        inputs=(), out_regs=2, node=0, thread=0, max_fires=0,
        wants_version=True, on_epoch=on_epoch)]

    def make_stage_fn(stage):
        cache = make_stage_cache(stage, sstaged.group_size,
                                 sstaged.cache_len, cache_spec)
        sampler = None
        if stage.last and sampling is not None:
            from repro.serve.sampler import SamplerStream

            # the sampler key stream is closure state of the LAST stage
            # actor (resident in that stage's worker), advanced once per
            # token-producing fire — fires are FIFO in submission order,
            # so the stream is identical across runtimes and backends
            sampler = SamplerStream(sampling, sstaged.cfg.vocab_size)

        def run_stage(payload):
            work = payload["work"]
            xin = payload.get("x")
            if xin is None:                       # first stage: token ids in
                xin = _work_input(work)
            xout = serve_stage_apply(stage, cache, work, xin)
            if stage.last:
                return {"work": work,
                        "result": _finish_round_item(sampler, work, xout)}
            return {"work": work, "x": xout}
        return run_stage

    for s, stage in enumerate(sstaged.stages):
        fn = make_stage_fn(stage)
        if fn_wrap is not None:
            fn = fn_wrap(s, fn)
        specs.append(ActorSpec(
            name=f"stage{s}", fn=fn,
            inputs=("admit",) if s == 0 else (f"stage{s-1}",),
            out_regs=regs[s], node=s + 1, thread=0, max_fires=0))
    return specs, f"stage{S - 1}"


class ServeSpecBuilder(_SpecBuilderBase):
    """Picklable builder of the continuous-batching serve actor graph.
    ``cache_spec``/``sampling`` are frozen dataclasses, so the paged-pool
    geometry and the sampler seed ride the pickle into process workers."""

    def __init__(self, regs=None, fn_wrap=None, staged=None, recipe=None,
                 cache_spec=None, sampling=None):
        super().__init__(staged=staged, recipe=recipe)
        self.regs = None if regs is None else list(regs)
        self.fn_wrap = fn_wrap
        self.cache_spec = cache_spec
        self.sampling = sampling

    def __call__(self):
        return serve_stage_actor_specs(self.staged, regs=self.regs,
                                       fn_wrap=self.fn_wrap,
                                       cache_spec=self.cache_spec,
                                       sampling=self.sampling)


class ServePipelineExecutor(_StagedExecutorBase):
    """Run a :class:`repro.core.lowering.ServeStagedProgram` as a pipelined
    continuous-batching decode engine.

    The actor graph persists across rounds; per-stage, per-group caches are
    closure state inside each ``stage{s}`` actor, resident in the worker
    that owns the stage (under ``runtime="processes"``, a real process —
    the caches never cross a process boundary). Each :meth:`run_round` is
    one epoch: the round's work items travel in ``ctx``, the per-actor fire
    bound is the round's work count, and the last stage's logits are
    collected in emission order. ``regs[s]`` is stage s's out-register
    quota (default ``max(1, S - s)``, the forward-pipeline schedule);
    quota back-pressure alone bounds how many groups are in flight.

    Instrumentation mirrors the other executors (``last_makespan``,
    ``last_history``, ``last_peak_regs``, ``last_edge_bytes``) plus
    ``rounds`` counted over the session.
    """

    def __init__(self, sstaged, regs: Optional[Sequence[int]] = None,
                 fn_wrap: Optional[Callable] = None,
                 runtime: str = "threads", recipe=None,
                 cache_spec=None, sampling=None):
        super().__init__(sstaged, [], 1, regs, fn_wrap,
                         runtime=runtime, recipe=recipe)
        self.sstaged = sstaged
        self.cache_spec = cache_spec
        self.sampling = sampling
        self.rounds = 0

    def _make_builder(self):
        return ServeSpecBuilder(regs=self.regs, fn_wrap=self.fn_wrap,
                                staged=self.sstaged, recipe=self.recipe,
                                cache_spec=self.cache_spec,
                                sampling=self.sampling)

    def run_round(self, work: Sequence, timeout: float = 300.0) -> List:
        """Stream ``work`` (PrefillWork/PrefillChunkWork/DecodeWork items)
        through the stage actors; returns one entry per item in submission
        order — the last stage's logits, or ``{"logits", "tokens"}`` dicts
        when sampling is on."""
        from jax.profiler import TraceAnnotation

        if not work:
            return []
        work = list(work)
        n = len(work)
        S = self.sstaged.num_stages
        fires = {"admit": n}
        fires.update({f"stage{s}": n for s in range(S)})
        with TraceAnnotation("runtime.round"):
            outs = self._run_rt({"admit": work}, fires, timeout)
        if len(outs) != n:
            raise RuntimeError(f"collected {len(outs)} round results, "
                               f"expected {n}")
        self.rounds += 1
        # the final stage fires in FIFO submission order in one worker
        return [o["result"] for o in outs]
