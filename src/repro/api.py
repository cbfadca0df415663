"""repro.api — the single compile/run frontend (paper §2, §4).

OneFlow's central usability claim: the user writes ONE logical graph with
placement and SBP annotations, and a single compile step produces the
runnable artifact — the framework, not the user, decides how to lower and
execute it. This module is that frontend for the reproduction. The four
historical entry paths (``lower_plan``, ``lower_stages`` +
``ActorPipelineExecutor``, ``make_graph_train_step``,
``make_pipeline_train_step`` + ``TrainPipelineExecutor``) are all reachable
through one call::

    from repro import api

    sess = api.compile(g, mode="train", params=init_params,
                       num_microbatches=8,
                       optimizer=OptimizerSpec.adamw(grad_clip=1.0))
    for batch in batches:
        res = sess.step(**batch)          # StepResult(loss, metrics, ...)
    sess.params, sess.opt_state, print(sess.describe())

Every option is declarative and inferred when omitted: ``plan`` via
:func:`repro.core.planner.plan`, the stage ``partition`` via
:func:`repro.core.graph.partition_stages` (user ``g.stage(k)`` annotations or
cost-balanced), register quotas via
:func:`repro.runtime.pipeline.plan_registers` (the paper's compile-time
resource planning, §2.3), ``microbatch_inputs`` as the non-param graph
inputs in train mode.

``backend="actors"`` runs stages as actors (1F1B emerging from register
quotas, §4.3/§6.5) on a runtime chosen by ``runtime=``: ``"threads"`` drives
every actor on OS threads in this process, ``"processes"`` gives each
pipeline stage its own worker process (paper Fig 7/8 — the node field of the
64-bit actor address becomes a real OS process) with payloads crossing
stages over a real transport. ``backend="monolithic"`` runs the same
:class:`Session` surface over whole-graph jitted programs (``lower_plan`` /
``lower_train_plan``) with identical microbatch chunking, so
pipeline-vs-monolithic bit-identity checks are one-liners
(:func:`assert_sessions_match`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.core.graph import (LogicalGraph, StagePartition, partition_stages)
from repro.core.lowering import (OptimizerSpec, PrecisionPolicy, lower_plan,
                                 lower_serve_stages, lower_stages,
                                 lower_train_plan, lower_train_stages,
                                 reassemble_sinks, split_microbatches)
from repro.core.planner import Plan, plan as plan_sbp
from repro.launch.mesh import as_auto
from repro.runtime.base import RUNTIME_KINDS
from repro.runtime.pipeline import (
    ActorPipelineExecutor, InlineServeEngine, PipelinePlan,
    ServePipelineExecutor, TrainPipelineExecutor, check_run_inputs,
    plan_registers)
from repro.runtime.recipes import (InferRecipe, MeshSpec, ServeRecipe,
                                   TrainRecipe)

MODES = ("infer", "train", "serve")
BACKENDS = ("actors", "monolithic")

#: named register-quota policies accepted by ``compile(regs=...)`` — the
#: paper's schedules as declarative one-words instead of hand-built lists
REG_POLICIES = ("1f1b", "gpipe", "serial")


@dataclasses.dataclass
class StepResult:
    """One training step's outcome, uniform across backends.

    ``metrics`` always carries ``step`` (0-based index of the step just
    taken), ``lr`` (the schedule resolved at that step), and ``grad_norm``
    (pre-clip global norm; None when clipping is off). Actor-backend sessions
    add ``makespan`` (wall-clock seconds) and ``peak_inflight`` (peak forward
    registers in use — the in-flight microbatch count the quota bounds).
    """

    loss: Any
    metrics: Dict[str, Any]
    grads: Dict[str, Any]
    params: Dict[str, Any]


def _canonical_params(graph: LogicalGraph, params: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """Reorder a param dict into graph-input order — the canonical order
    both backends use for the global-norm sum, so clipping is bit-identical
    no matter how the caller built the dict."""
    input_names = [t.name for t in graph.inputs]
    unknown = sorted(set(params) - set(input_names))
    if unknown:
        raise ValueError(f"params entries are not graph inputs: {unknown}")
    return {n: params[n] for n in input_names if n in params}


class _MonolithicInferEngine:
    """``backend="monolithic"`` inference: one whole-graph jitted program
    (:func:`repro.core.lowering.lower_plan`), run once per microbatch chunk
    with the same :func:`split_microbatches` chunking as the actor pipeline
    so the two backends agree bitwise."""

    def __init__(self, graph: LogicalGraph, plan: Plan, mesh,
                 microbatch_inputs: Sequence[str], num_microbatches: int):
        self.graph = graph
        self.program = lower_plan(graph, plan, mesh)
        self.input_names = [t.name for t in graph.inputs]
        self.microbatch_inputs = list(microbatch_inputs)
        self.num_microbatches = num_microbatches
        for n in self.microbatch_inputs:
            if n not in self.input_names:
                raise ValueError(f"{n} is not a graph input")
        self.last_makespan: Optional[float] = None

    def run(self, inputs: Dict[str, Any], timeout: float = 0.0) -> Tuple:
        check_run_inputs(inputs, self.input_names)
        t0 = time.perf_counter()
        if not self.microbatch_inputs:
            chunks = [dict(inputs)]
        else:
            chunks = split_microbatches(inputs, self.microbatch_inputs,
                                        self.num_microbatches)
        mb = set(self.microbatch_inputs)
        sink_names = [t.name for t in self.program.sinks]
        per_chunk = [
            dict(zip(sink_names,
                     self.program(*(c[n] if n in mb else inputs[n]
                                    for n in self.input_names))))
            for c in chunks]
        results = reassemble_sinks(self.graph, self.program.sinks,
                                   self.microbatch_inputs, per_chunk)
        self.last_makespan = time.perf_counter() - t0
        return results


class _MonolithicTrainEngine:
    """``backend="monolithic"`` training: whole-graph value-and-grad
    (:func:`repro.core.lowering.lower_train_plan`) with the exact microbatch
    chunking, fp32 accumulation, canonical-order global-norm clipping, and
    :class:`OptimizerSpec` kernels of the actor pipeline — the reference its
    numbers are checked against, owned by the same :class:`Session` surface.
    """

    def __init__(self, graph: LogicalGraph, plan: Plan, mesh,
                 params: Dict[str, Any], microbatch_inputs: Sequence[str],
                 num_microbatches: int, optimizer: OptimizerSpec,
                 loss=None):
        self.graph = graph
        self.params = _canonical_params(graph, params)
        self.param_names = tuple(self.params)
        self.optimizer = optimizer
        self._scaling = optimizer.loss_scaling is not None
        self.vg = lower_train_plan(graph, plan, mesh, list(self.param_names),
                                   loss=loss, scaled=self._scaling)
        self.input_names = [t.name for t in graph.inputs]
        self.microbatch_inputs = list(microbatch_inputs)
        self.num_microbatches = num_microbatches
        self._opt_state = None
        self.step_count = 0
        self.last_grad_norm = None
        self.last_makespan: Optional[float] = None
        # loss-scaling mirror — same trajectory as the pipelined scale actor
        self.loss_scale = (optimizer.initial_scale()
                           if self._scaling else None)
        self.scale_good_steps = 0
        self.last_skipped = False
        self.last_scale = None
        # mixed precision: fp32 masters (flat ZeRO shards or dense) are the
        # optimizer's view; ``_compute`` is the cast copy fwd/bwd see
        self._masters = None
        self._compute = None
        self._refresh_masters()

    def _refresh_masters(self) -> None:
        import jax.numpy as jnp

        opt = self.optimizer
        if not opt.mixed_precision:
            self._masters = self._compute = None
            return
        if opt.zero:
            self._masters = opt.shard_masters(self.params)
            self._compute = opt.gather_params(self._masters,
                                              dtype=opt.compute_dtype)
            # re-canonicalize params through the same shard/gather (bitwise
            # identity for fp32 inputs: pad-then-truncate is pure layout)
            self.params = opt.gather_params(self._masters)
        else:
            self._masters = {n: jnp.asarray(v).astype(jnp.float32)
                             for n, v in self.params.items()}
            self._compute = {n: v.astype(jnp.dtype(opt.compute_dtype))
                             for n, v in self._masters.items()}
            self.params = dict(self._masters)

    @property
    def opt_state(self):
        """Merged (full-tensor) optimizer state — flat ZeRO shards are
        gathered so the surface is partition- and zero-agnostic."""
        st = self._opt_state
        if st is None or not self.optimizer.zero:
            return st
        return self.optimizer.merge_states([st])

    def load_params(self, params: Dict[str, Any]) -> None:
        missing = [n for n in self.param_names if n not in params]
        if missing:
            raise ValueError(f"missing params: {missing}")
        self.params = {n: params[n] for n in self.param_names}
        self._refresh_masters()

    def load_state(self, params: Optional[Dict[str, Any]] = None,
                   opt_state=None, step: Optional[int] = None) -> None:
        """Restore full training state (e.g. from a snapshot): params,
        optimizer state, and the step counter the lr schedule indexes.
        ``opt_state`` is always the merged full-tensor form; a ZeRO
        optimizer re-shards it flat on arrival."""
        if params is not None:
            self.load_params(params)
        if opt_state is not None:
            if not self.optimizer.stateful:
                raise ValueError(
                    "opt_state= for a stateless optimizer "
                    f"({self.optimizer.kind})")
            if self.optimizer.zero:
                opt_state = self.optimizer.split_state(
                    opt_state, {0: list(self.param_names)})[0]
            self._opt_state = opt_state
        if step is not None:
            self.step_count = int(step)

    def step(self, data_inputs: Dict[str, Any], timeout: float = 0.0):
        import numpy as np

        import jax.numpy as jnp

        from repro.core.lowering import loss_scale_update
        from repro.optim.adamw import (clip_scale, global_norm_from_partials,
                                       scale_grad, sqnorm_partials)

        check_run_inputs(
            data_inputs,
            [n for n in self.input_names if n not in self.params],
            owned=self.param_names)
        t0 = time.perf_counter()
        chunks = split_microbatches(data_inputs, self.microbatch_inputs,
                                    self.num_microbatches)
        mb = set(self.microbatch_inputs)
        opt = self.optimizer
        compute = self._compute if self._compute is not None else self.params
        loss_total, grads = None, None
        for chunk in chunks:
            vals = [chunk[n] if n in mb
                    else (compute[n] if n in compute
                          else data_inputs[n])
                    for n in self.input_names]
            if self._scaling:
                loss_vec, g = self.vg(np.float32(self.loss_scale), *vals)
            else:
                loss_vec, g = self.vg(*vals)
            ls = jnp.sum(loss_vec)
            loss_total = ls if loss_total is None else loss_total + ls
            g32 = [x.astype(jnp.float32) for x in g]
            grads = (g32 if grads is None
                     else [a + b for a, b in zip(grads, g32)])
        gdict = dict(zip(self.param_names, grads))
        if self._scaling:
            # unscale ONCE after accumulation (exact for power-of-two
            # scales) — same op order as the pipelined acc actors
            inv = np.float32(np.float32(1.0) / np.float32(self.loss_scale))
            gdict = {n: scale_grad(g, inv) for n, g in gdict.items()}
        need_norm = bool(opt.grad_clip) or opt.dynamic_scaling
        if need_norm:
            norm = global_norm_from_partials(sqnorm_partials(gdict),
                                             self.param_names)
            cscale = clip_scale(norm, opt.grad_clip)
            gdict = {n: scale_grad(g, cscale) for n, g in gdict.items()}
            self.last_grad_norm = norm
        self.last_scale = self.loss_scale
        if opt.dynamic_scaling:
            finite = bool(np.isfinite(np.float32(norm)))
            skip, nxt, good = loss_scale_update(
                opt.precision, self.loss_scale, self.scale_good_steps,
                finite)
            self.loss_scale, self.scale_good_steps = nxt, good
            self.last_skipped = skip
            if skip:
                # non-finite grads: leave params/masters/state untouched —
                # the same no-op the pipelined opt actors perform
                self.last_makespan = time.perf_counter() - t0
                return loss_total, {}, dict(self.params)
        else:
            self.last_skipped = False
        masters = self._masters if self._masters is not None else dict(
            self.params)
        if opt.stateful and self._opt_state is None:
            self._opt_state = opt.init_state(masters)
        new_masters, self._opt_state = opt.update(
            masters, gdict, self._opt_state, opt.lr_at(self.step_count))
        if opt.mixed_precision:
            self._masters = new_masters
            if opt.zero:
                self.params = opt.gather_params(new_masters)
                self._compute = opt.gather_params(new_masters,
                                                  dtype=opt.compute_dtype)
            else:
                self.params = dict(new_masters)
                self._compute = {
                    n: v.astype(jnp.dtype(opt.compute_dtype))
                    for n, v in new_masters.items()}
        else:
            self.params = new_masters
        self.step_count += 1
        self.last_makespan = time.perf_counter() - t0
        return loss_total, gdict, dict(self.params)

    def opt_state_bytes(self) -> Dict[int, int]:
        """Monolithic counterpart of
        :meth:`repro.runtime.pipeline.TrainPipelineExecutor.opt_state_bytes`:
        one entry (stage 0) of per-device optimizer-held fp32 bytes."""
        import numpy as np

        opt = self.optimizer
        zero_dp = opt.zero_dp if opt.zero else 1
        total = 0
        st = self._opt_state
        if st is not None:
            for tree in (st.mu, st.nu):
                total += sum(int(np.asarray(v).nbytes)
                             for v in tree.values())
        if opt.mixed_precision:
            for n in self.param_names:
                nelem = int(np.asarray(self.params[n]).size)
                total += -(-nelem // zero_dp) * zero_dp * 4
        return {0: total // zero_dp}


class Session:
    """The uniform run/step surface every compile path returns.

    * ``mode="infer"``: :meth:`run` maps graph-input values to a dict of
      sink values (named by sink tensor).
    * ``mode="train"``: :meth:`step` takes the non-param inputs and returns
      a :class:`StepResult`; the session owns ``params`` and any optimizer
      state across steps.

    ``describe()`` reports the SBP plan, the stage partition with register
    quotas, and the simulated register plan (building on
    :meth:`repro.core.graph.StagePartition.describe`) — the compiled
    artifact, human-readable. ``history`` accumulates one record per
    :meth:`run`/:meth:`step` call.

    Sessions are built by :func:`compile`, never directly.
    """

    def __init__(self, *, graph: LogicalGraph, mode: str, backend: str,
                 engine, plan: Plan, partition: Optional[StagePartition],
                 regs: Optional[List[int]], reg_plan: Optional[PipelinePlan],
                 optimizer: Optional[OptimizerSpec],
                 microbatch_inputs: List[str], num_microbatches: int,
                 timeout: float = 300.0, runtime: Optional[str] = None):
        self.graph = graph
        self.mode = mode
        self.backend = backend
        self.runtime = runtime        # "threads"/"processes"; None: monolithic
        self.plan = plan
        self.partition = partition
        self.regs = regs
        self.reg_plan = reg_plan
        self.optimizer = optimizer
        self.microbatch_inputs = microbatch_inputs
        self.num_microbatches = num_microbatches
        self.timeout = timeout
        self.history: List[Dict[str, Any]] = []
        self.static_report = None     # repro.analysis.StaticReport
        self._engine = engine
        self._sinks = graph.sinks()

    # -- the executor/engine underneath, for callers that need the guts ----
    @property
    def executor(self):
        """The backing executor/engine: an
        :class:`repro.runtime.pipeline.ActorPipelineExecutor` or
        :class:`~repro.runtime.pipeline.TrainPipelineExecutor` for
        ``backend="actors"``, the monolithic engine otherwise."""
        return self._engine

    @property
    def params(self) -> Optional[Dict[str, Any]]:
        """Current trainable params (None for inference sessions)."""
        if self.mode != "train":
            return None
        return dict(self._engine.params)

    @property
    def opt_state(self):
        """Optimizer state over all params (merged across stages for the
        actor backend; None for SGD or inference)."""
        if self.mode != "train":
            return None
        return self._engine.opt_state

    @property
    def step_count(self) -> int:
        return getattr(self._engine, "step_count", 0)

    @property
    def last_makespan(self) -> Optional[float]:
        return self._engine.last_makespan

    @property
    def last_edge_bytes(self) -> Dict[Any, int]:
        """Per-edge serialized payload bytes from the last step/run —
        ``{(producer, consumer): bytes}`` from the actor runtime; empty for
        monolithic engines (one program, no edges)."""
        return dict(getattr(self._engine, "last_edge_bytes", None) or {})

    def load_params(self, params: Dict[str, Any]) -> None:
        """Replace the session-owned params (e.g. checkpoint restore);
        optimizer state is untouched."""
        if self.mode != "train":
            raise RuntimeError("load_params() on an inference session")
        self._engine.load_params(params)

    def load_state(self, params: Optional[Dict[str, Any]] = None,
                   opt_state=None, step: Optional[int] = None) -> None:
        """Restore full training state — params, merged optimizer state,
        and the step counter — e.g. from
        :func:`repro.runtime.snapshot.load_snapshot`. Each piece is optional
        and independent; the actor backend re-splits ``opt_state`` by *this*
        session's stage partition, so a snapshot taken under one partition
        restores onto another (elastic resume)."""
        if self.mode != "train":
            raise RuntimeError("load_state() on an inference session")
        self._engine.load_state(params=params, opt_state=opt_state,
                                step=step)

    def close(self) -> None:
        """Release the engine's workers (actor threads or worker processes).
        Monolithic engines have none; the call is a no-op there."""
        close = getattr(self._engine, "close", None)
        if close is not None:
            close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def run(self, **inputs) -> Dict[str, Any]:
        """Execute the compiled inference program over ``inputs`` (one
        keyword per graph input) and return ``{sink name: value}``."""
        if self.mode != "train":
            outs = self._engine.run(inputs, timeout=self.timeout)
            self.history.append({"kind": "run",
                                 "makespan": self._engine.last_makespan})
            return {t.name: v for t, v in zip(self._sinks, outs)}
        raise RuntimeError(
            "run() on a train-mode session; use step(**batch) "
            "(or compile with mode='infer')")

    def step(self, **batch) -> StepResult:
        """Run one training step over the session-owned params and return a
        :class:`StepResult`. ``batch`` maps every non-param graph input to
        its value; the names in ``microbatch_inputs`` are split into
        ``num_microbatches`` chunks along axis 0."""
        if self.mode != "train":
            raise RuntimeError(
                "step() on an infer-mode session; use run(**inputs) "
                "(or compile with mode='train', params=...)")
        index = self._engine.step_count
        loss, grads, params = self._engine.step(batch, timeout=self.timeout)
        metrics = {
            "step": index,
            "lr": (self.optimizer.lr_at(index)
                   if self.optimizer is not None else None),
            "grad_norm": self._engine.last_grad_norm,
            "makespan": self._engine.last_makespan,
        }
        if (self.optimizer is not None
                and self.optimizer.loss_scaling is not None):
            ls = getattr(self._engine, "last_scale", None)
            metrics["loss_scale"] = None if ls is None else float(ls)
            metrics["skipped"] = bool(getattr(self._engine, "last_skipped",
                                              False))
        if self.backend == "actors":
            metrics["peak_inflight"] = self._engine.peak_inflight_activations
        # history holds host floats only, so a long training loop never
        # pins device arrays
        gn = metrics["grad_norm"]
        self.history.append({"kind": "step", "loss": float(loss), **metrics,
                             "grad_norm": None if gn is None else float(gn)})
        return StepResult(loss=loss, metrics=metrics, grads=grads,
                          params=params)

    def describe(self) -> str:
        """Human-readable report of the compiled artifact: graph shape, SBP
        plan, stage partition + register quotas, optimizer."""
        g = self.graph
        rt = f" runtime={self.runtime}" if self.runtime is not None else ""
        lines = [f"=== repro.api session: mode={self.mode} "
                 f"backend={self.backend}{rt} ===",
                 f"graph: {len(g.ops)} ops, "
                 f"inputs {[t.name for t in g.inputs]}, "
                 f"sinks {[t.name for t in self._sinks]}",
                 f"microbatches: {self.num_microbatches} over "
                 f"{self.microbatch_inputs or '(none)'}"]
        if self.mode == "train":
            opt = self.optimizer
            lines.append(
                f"optimizer: {opt.kind} (grad_clip={opt.grad_clip}, "
                f"stateful={opt.stateful})" if opt is not None
                else "optimizer: none")
            if opt is not None and opt.mixed_precision:
                scaling = opt.loss_scaling
                lines.append(
                    f"precision: compute={opt.compute_dtype} "
                    f"masters=float32 "
                    f"loss_scale={'off' if scaling is None else scaling}")
            if opt is not None and opt.zero:
                lines.append(
                    f"zero: dp={opt.zero_dp} — flat (dp, 1, chunk) fp32 "
                    "master/moment shards held by the opt actors")
            bytes_fn = getattr(self._engine, "opt_state_bytes", None)
            if opt is not None and opt.stateful and bytes_fn is not None:
                per = bytes_fn()
                if per:
                    per_s = " ".join(f"stage{s}={per[s]}"
                                     for s in sorted(per))
                    lines.append(
                        "optimizer-state bytes/device: "
                        f"{per_s} (total {sum(per.values())})")
        lines.append(self.plan.describe())
        if self.partition is not None:
            lines.append(self.partition.describe(g, regs=self.regs))
        else:
            lines.append("single whole-graph jitted program "
                         "(no stage partition)")
        if self.reg_plan is not None:
            rp = self.reg_plan
            lines.append(
                f"register plan (simulated): quota={rp.regs[0]} "
                f"makespan={rp.makespan:.1f} "
                f"bubble={rp.bubble_fraction:.2f}")
        if self.static_report is not None:
            lines.append(self.static_report.describe())
        return "\n".join(lines)

    def __repr__(self):
        return (f"Session(mode={self.mode!r}, backend={self.backend!r}, "
                f"stages={self.partition.num_stages if self.partition else 1}, "
                f"num_microbatches={self.num_microbatches})")


# ---------------------------------------------------------------------------
# mode="serve": continuous-batching autoregressive decode (ROADMAP "serving
# batching" seam — stage = model shard, microbatch = request group).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeRequest:
    """One generation request: prompt token ids + how many tokens to decode
    (the first generated token, from the prefill logits, counts)."""

    tokens: Any
    max_new_tokens: int


class ServeSession:
    """The serving counterpart of :class:`Session`: pipelined,
    continuously-batched greedy decode over the actor runtime.

    :meth:`generate` runs a set of :class:`ServeRequest`\\ s to completion:
    requests are packed into ``num_groups * group_size`` decode slots, each
    round advances every live group by one token (one :class:`DecodeWork`
    per group streamed down the stage actors), finished requests retire
    their slot and queued ones are admitted mid-flight with a
    :class:`PrefillWork` that scatters the new request's caches into the
    group cache. Retired/empty slots are *parked*: they decode a dummy
    token at the reserved position ``cache_len - 1``, which no live
    request's attention window ever reaches, so the group program keeps one
    fixed shape and nothing is masked inside the model.

    Mirrors the :class:`Session` conventions: ``describe()`` reports the
    compiled artifact, ``history`` accumulates one record per round, and
    ``executor`` exposes the backing engine.
    """

    def __init__(self, *, cfg, mesh, backend: str, engine, sstaged,
                 num_groups: int, group_size: int, cache_len: int,
                 max_prompt_len: int, max_new_tokens: int,
                 regs: Optional[List[int]], timeout: float = 300.0,
                 runtime: Optional[str] = None, cache: str = "dense",
                 cache_spec=None, sampling=None,
                 prefill_chunk: Optional[int] = None,
                 share_prefix: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        self.mode = "serve"
        self.backend = backend
        self.runtime = runtime        # "threads"/"processes"; None: monolithic
        self.sstaged = sstaged
        self.num_groups = num_groups
        self.group_size = group_size
        self.cache_len = cache_len
        self.max_prompt_len = max_prompt_len
        self.max_new_tokens = max_new_tokens
        self.regs = regs
        self.timeout = timeout
        self.cache = cache            # "dense" | "paged"
        self.cache_spec = cache_spec  # PagedCacheSpec when paged
        self.sampling = sampling      # SamplingSpec; None: greedy
        self.prefill_chunk = prefill_chunk
        self.share_prefix = share_prefix
        self.history: List[Dict[str, Any]] = []
        self.last_stats: Optional[Dict[str, Any]] = None
        self.static_report = None     # repro.analysis.StaticReport
        self._engine = engine

    @property
    def executor(self):
        """The backing engine: a
        :class:`repro.runtime.pipeline.ServePipelineExecutor` for
        ``backend="actors"``, the inline monolithic engine otherwise."""
        return self._engine

    @property
    def last_makespan(self) -> Optional[float]:
        return self._engine.last_makespan

    def close(self) -> None:
        """Release the engine's workers (no-op for the inline engine)."""
        close = getattr(self._engine, "close", None)
        if close is not None:
            close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    @staticmethod
    def _normalize(requests) -> List[ServeRequest]:
        out = []
        for r in requests:
            if isinstance(r, ServeRequest):
                out.append(r)
            else:
                toks, gen = r
                out.append(ServeRequest(toks, int(gen)))
        return out

    def generate(self, requests) -> List[Any]:
        """Run ``requests`` (ServeRequests or ``(tokens, max_new_tokens)``
        pairs) to completion with continuous batching; returns one int32
        token array per request, in submission order. Round planning and
        slot/page bookkeeping live in
        :class:`repro.serve.admission.AdmissionScheduler`; this loop only
        validates, drives the engine, and turns round results into
        tokens."""
        import numpy as np

        from repro.serve.admission import AdmissionScheduler

        reqs = self._normalize(requests)
        V = self.cfg.vocab_size
        prompts = []
        for i, r in enumerate(reqs):
            toks = np.asarray(r.tokens, dtype=np.int32)
            if toks.ndim != 1 or toks.size == 0:
                raise ValueError(f"request {i}: prompt must be a non-empty "
                                 f"1-d token array, got shape {toks.shape}")
            if toks.size > self.max_prompt_len:
                raise ValueError(
                    f"request {i}: prompt length {toks.size} exceeds "
                    f"max_prompt_len={self.max_prompt_len}")
            if (toks < 0).any() or (toks >= V).any():
                raise ValueError(f"request {i}: prompt ids must be in "
                                 f"[0, {V})")
            if not (1 <= r.max_new_tokens <= self.max_new_tokens):
                raise ValueError(
                    f"request {i}: max_new_tokens={r.max_new_tokens} must "
                    f"be in [1, {self.max_new_tokens}]")
            prompts.append(toks)

        pool = None
        if self.cache == "paged":
            from repro.serve.paged_cache import PagePool

            pool = PagePool(self.cache_spec)
        sched = AdmissionScheduler(
            prompts, [r.max_new_tokens for r in reqs],
            num_groups=self.num_groups, group_size=self.group_size,
            cache_len=self.cache_len, pool=pool,
            prefill_chunk=self.prefill_chunk,
            share_prefix=self.share_prefix)
        t0 = time.perf_counter()
        while not sched.done():
            work, meta = sched.plan_round()
            results = self._engine.run_round(work, timeout=self.timeout)
            for m, res in zip(meta, results):
                sched.absorb(m, self._pick_tokens(m, res))
            self.history.append({"kind": "round", "items": len(work),
                                 "makespan": self._engine.last_makespan})

        wall = time.perf_counter() - t0
        total = sum(len(o) for o in sched.outputs)
        self.last_stats = {
            "requests": len(reqs), "tokens": total,
            "rounds": self._engine.rounds, "wall_s": wall,
            "tok_per_s": total / wall if wall > 0 else float("inf"),
            "admitted_mid_flight": sched.admitted_mid_flight,
        }
        if pool is not None:
            self.last_stats["peak_pages"] = pool.peak_pages
            self.last_stats["shared_pages"] = sched.shared_pages
        self.history.append({"kind": "generate", **self.last_stats})
        return [np.asarray(o, np.int32) for o in sched.outputs]

    def _pick_tokens(self, m, res):
        """One round result -> the item's token vector (``None`` for a
        non-final chunk). With sampling on, the engine already sampled in
        the last stage; otherwise greedy the logits here, exactly the PR-5
        driver-side path."""
        import numpy as np

        from repro.train.steps import greedy_from_logits

        if self.sampling is not None:
            toks = res["tokens"]
            return None if toks is None else np.asarray(toks)
        if m[0] == "chunk":
            if not m[3]:
                return None
            res = res[-1]        # the chunk's last position feeds the head
        return np.asarray(greedy_from_logits(res, self.cfg.vocab_size))

    def cache_bytes(self) -> int:
        """Analytic persistent cache bytes across all stages: the full
        dense reservation (``num_groups`` group blocks) or the paged pool
        (slabs + page table + cursors), from ``jax.eval_shape`` — nothing
        is allocated."""
        import jax
        import jax.numpy as jnp

        from repro.serve.paged_cache import dense_bytes, slab_bytes

        total = 0
        tok = jax.ShapeDtypeStruct((self.group_size,), jnp.int32)
        for stage in self.sstaged.stages:
            template = jax.eval_shape(stage.init_caches, tok)
            if self.cache == "paged":
                total += slab_bytes(template, self.cache_spec)
            else:
                total += dense_bytes(template, self.num_groups)
        return total

    def describe(self) -> str:
        """Human-readable report of the compiled serving artifact."""
        cfg = self.cfg
        rt = f" runtime={self.runtime}" if self.runtime is not None else ""
        lines = [f"=== repro.api session: mode=serve "
                 f"backend={self.backend}{rt} ===",
                 f"model: {cfg.name} ({cfg.num_layers} layers, "
                 f"d_model={cfg.d_model}, vocab={cfg.vocab_size} "
                 f"padded to {cfg.padded_vocab()})",
                 f"slots: {self.num_groups} groups x {self.group_size} "
                 f"(cache_len={self.cache_len}, "
                 f"max_prompt_len={self.max_prompt_len}, "
                 f"max_new_tokens={self.max_new_tokens})",
                 self.sstaged.describe()]
        if self.cache == "paged":
            sp = self.cache_spec
            extra = (f" prefill_chunk={self.prefill_chunk}"
                     if self.prefill_chunk is not None else "")
            lines.insert(3, f"cache: paged ({sp.num_pages} pages x "
                            f"page_len={sp.page_len}, "
                            f"{sp.pages_per_req} pages/request, "
                            f"share_prefix={self.share_prefix}){extra}")
        else:
            lines.insert(3, "cache: dense (one group block per slot group)")
        if self.sampling is not None:
            sp = self.sampling
            lines.insert(4, f"sampling: temperature={sp.temperature} "
                            f"top_k={sp.top_k} top_p={sp.top_p} "
                            f"seed={sp.seed}")
        if self.regs is not None:
            lines.append(f"register quotas: {self.regs}")
        if self.static_report is not None:
            lines.append(self.static_report.describe())
        return "\n".join(lines)

    def __repr__(self):
        return (f"ServeSession(backend={self.backend!r}, "
                f"stages={self.sstaged.num_stages}, "
                f"groups={self.num_groups}x{self.group_size})")


def _serve_options(*, num_groups, group_size, cache_len, max_prompt_len,
                   max_new_tokens, cache, page_len, num_pages, sampling,
                   prefill_chunk, tp: int):
    """Resolve defaults and validate every serve-only compile option at
    compile time (a bad geometry must fail here, not as a shape error in
    the middle of ``generate``). Returns ``(num_groups, group_size,
    cache_len, max_prompt_len, max_new_tokens, cache, cache_spec)``."""
    import math

    num_groups = 2 if num_groups is None else num_groups
    group_size = 2 if group_size is None else group_size
    max_prompt_len = 64 if max_prompt_len is None else max_prompt_len
    max_new_tokens = 64 if max_new_tokens is None else max_new_tokens
    if num_groups < 1 or group_size < 1:
        raise ValueError(f"num_groups={num_groups} and "
                         f"group_size={group_size} must be >= 1")
    if max_prompt_len < 1 or max_new_tokens < 1:
        raise ValueError(f"max_prompt_len={max_prompt_len} and "
                         f"max_new_tokens={max_new_tokens} must be >= 1")
    if cache_len is None:
        cache_len = max_prompt_len + max_new_tokens + 9
        cache_len += -cache_len % tp
    elif cache_len <= max_prompt_len + max_new_tokens:
        # the last cache position is the parking slot for retired requests
        raise ValueError(
            f"cache_len={cache_len} must exceed max_prompt_len + "
            f"max_new_tokens = {max_prompt_len + max_new_tokens} "
            "(the final position is reserved for parked slots); lower "
            "max_prompt_len= or max_new_tokens=, or raise cache_len=")
    cache = "dense" if cache is None else cache
    if cache not in ("dense", "paged"):
        raise ValueError(f"cache={cache!r}; expected 'dense' or 'paged'")
    if sampling is not None:
        from repro.serve.sampler import SamplingSpec
        if not isinstance(sampling, SamplingSpec):
            raise ValueError(
                "sampling= takes a repro.serve.sampler.SamplingSpec, got "
                f"{type(sampling).__name__}")
    cache_spec = None
    if cache == "dense":
        paged_only = {"page_len": page_len, "num_pages": num_pages,
                      "prefill_chunk": prefill_chunk}
        bad = [k for k, v in paged_only.items() if v is not None]
        if bad:
            raise ValueError(f"{bad[0]}= requires cache='paged' (the dense "
                             "cache has no page geometry)")
    else:
        from repro.serve.paged_cache import PagedCacheSpec
        if page_len is None:
            # largest divisor of cache_len not exceeding 16
            page_len = max(d for d in range(1, min(16, cache_len) + 1)
                           if cache_len % d == 0)
        if page_len < 1 or cache_len % page_len:
            raise ValueError(
                f"page_len={page_len} must be a positive divisor of "
                f"cache_len={cache_len} (every mapped page must be fully "
                "overwritten by the admission prefill)")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk={prefill_chunk} must be >= 1")
        max_requests = num_groups * group_size
        pages_per_req = cache_len // page_len
        # worst-case single request: prompt + all decode writes must fit,
        # or admission could stall forever on an empty pool
        min_pages = math.ceil((max_prompt_len + max_new_tokens - 1)
                              / page_len)
        if num_pages is None:
            num_pages = max_requests * pages_per_req
        if num_pages < min_pages:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one worst-case request "
                f"({min_pages} pages of page_len={page_len} for "
                f"max_prompt_len + max_new_tokens - 1 = "
                f"{max_prompt_len + max_new_tokens - 1} positions)")
        cache_spec = PagedCacheSpec(page_len=page_len, num_pages=num_pages,
                                    max_requests=max_requests,
                                    pages_per_req=pages_per_req)
    return (num_groups, group_size, cache_len, max_prompt_len,
            max_new_tokens, cache, cache_spec)


def _compile_serve(cfg, *, backend: str, stages: Optional[int], regs,
                   params: Optional[Dict[str, Any]], mesh, fn_wrap,
                   timeout: float, num_groups: Optional[int],
                   group_size: Optional[int], cache_len: Optional[int],
                   max_prompt_len: Optional[int],
                   max_new_tokens: Optional[int],
                   runtime: str = "threads", cache: Optional[str] = None,
                   page_len: Optional[int] = None,
                   num_pages: Optional[int] = None, sampling=None,
                   prefill_chunk: Optional[int] = None,
                   check: str = "static") -> ServeSession:
    import jax

    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_mesh
    from repro.models.model_zoo import build_model
    from repro.models.transformer import stack_layout
    from repro.train.steps import plan_from_mesh

    if isinstance(cfg, str):
        from repro.configs.registry import get_config
        cfg = get_config(cfg)
    if not isinstance(cfg, ModelConfig):
        raise ValueError(
            "mode='serve' compiles a repro.configs.base.ModelConfig (or an "
            f"--arch name), got {type(cfg).__name__}")
    if mesh is None:
        mesh = make_mesh((1, 1), ("data", "model"))
    plan = plan_from_mesh(mesh)
    tp = plan.tp
    (num_groups, group_size, cache_len, max_prompt_len, max_new_tokens,
     cache, cache_spec) = _serve_options(
        num_groups=num_groups, group_size=group_size, cache_len=cache_len,
        max_prompt_len=max_prompt_len, max_new_tokens=max_new_tokens,
        cache=cache, page_len=page_len, num_pages=num_pages,
        sampling=sampling, prefill_chunk=prefill_chunk, tp=tp)
    if cache == "paged" and (tp != 1 or plan.dp != 1):
        raise ValueError(
            "cache='paged' requires a 1x1 mesh (the page gather/scatter "
            f"programs are single-device); got dp={plan.dp}, tp={tp}")

    lay = stack_layout(cfg)
    n_units = len(lay.prologue) + lay.n_periods
    if backend == "monolithic":
        if stages not in (None, 1):
            raise ValueError("backend='monolithic' serves the whole stack "
                             "as one stage; use backend='actors' for "
                             f"stages={stages}")
        stages = 1
    elif stages is None:
        stages = min(2, n_units)

    owned = params is None
    if owned:
        params = build_model(cfg, plan_from_mesh(mesh)).init(
            jax.random.PRNGKey(0))
    host_params = None
    if backend != "monolithic" and runtime == "processes":
        # workers re-lower from data: ship host copies of the params
        host_params = jax.device_get(params)
    # params built here are handed over: the lowering frees each stacked
    # body leaf once it is sliced into stages
    sstaged = lower_serve_stages(cfg, mesh, params, num_stages=stages,
                                 cache_len=cache_len,
                                 max_prompt_len=max_prompt_len,
                                 group_size=group_size,
                                 consume_params=owned)
    del params
    if isinstance(regs, str):
        regs = _policy_regs(regs, stages, num_groups)
    # shared-prefix pages assume a prompt prefix's cache values are
    # independent of the suffix — true for causal attention/SSM stacks, not
    # under MoE capacity routing (expert drop counts see the whole prompt)
    share_prefix = (cache == "paged"
                    and getattr(cfg, "num_experts", 0) == 0)
    if backend == "monolithic":
        if fn_wrap is not None:
            raise ValueError("fn_wrap requires backend='actors' "
                             "(there are no stage actors to wrap)")
        engine = InlineServeEngine(sstaged, cache_spec=cache_spec,
                                   sampling=sampling)
        regs = None
        runtime = None
    else:
        recipe = None
        if runtime == "processes":
            # the mesh travels as device ids (repro.runtime.recipes)
            recipe = ServeRecipe(cfg, host_params,
                                 num_stages=stages, cache_len=cache_len,
                                 max_prompt_len=max_prompt_len,
                                 group_size=group_size,
                                 mesh=MeshSpec.capture(mesh))
        engine = ServePipelineExecutor(sstaged, regs=regs, fn_wrap=fn_wrap,
                                       runtime=runtime, recipe=recipe,
                                       cache_spec=cache_spec,
                                       sampling=sampling)
        regs = engine.regs if engine.regs is not None else \
            _policy_regs("1f1b", stages, num_groups)
    sess = ServeSession(cfg=cfg, mesh=mesh, backend=backend, engine=engine,
                        sstaged=sstaged, num_groups=num_groups,
                        group_size=group_size, cache_len=cache_len,
                        max_prompt_len=max_prompt_len,
                        max_new_tokens=max_new_tokens, regs=regs,
                        timeout=timeout, runtime=runtime, cache=cache,
                        cache_spec=cache_spec, sampling=sampling,
                        prefill_chunk=prefill_chunk,
                        share_prefix=share_prefix)
    return _attach_static_report(sess, check)


def _resolve_partition(graph: LogicalGraph,
                       partition: Optional[StagePartition],
                       stages: Optional[int]) -> StagePartition:
    if partition is not None:
        if stages is not None and stages != partition.num_stages:
            raise ValueError(
                f"stages={stages} contradicts partition.num_stages="
                f"{partition.num_stages}; pass one or the other")
        return partition
    if stages is None and all(op.stage is None for op in graph.ops):
        raise ValueError(
            "graph has no stage annotations; pass stages= (a count for "
            "cost-balanced cutting) or partition=, or use "
            "backend='monolithic'")
    return partition_stages(graph, stages)


def _policy_regs(policy: str, num_stages: int, width: int) -> List[int]:
    """Map a :data:`REG_POLICIES` name to per-stage quotas. ``width`` is
    what ``"gpipe"`` admits everywhere: the microbatch count in graph
    modes, the request-group count in serve mode."""
    if policy == "1f1b":
        return [max(1, num_stages - s) for s in range(num_stages)]
    if policy == "gpipe":
        return [width] * num_stages
    if policy == "serial":
        return [1] * num_stages
    raise ValueError(f"unknown regs policy {policy!r}; "
                     f"pass one of {REG_POLICIES} or an explicit list")


def _resolve_regs(regs, partition: StagePartition, num_microbatches: int,
                  mode: str) -> Tuple[List[int], Optional[PipelinePlan]]:
    """Turn the declarative ``regs`` option into per-stage quotas.

    None -> compile-time resource planning (:func:`plan_registers`, §2.3);
    a policy name from :data:`REG_POLICIES` -> the corresponding schedule;
    an explicit sequence -> validated pass-through.
    """
    S = partition.num_stages
    if regs is None:
        bwd = 2.0 if mode == "train" else 0.0
        rp = plan_registers(S, num_microbatches, fwd_time=1.0,
                            bwd_time=max(bwd, 1e-3))
        return list(rp.regs), rp
    if isinstance(regs, str):
        return _policy_regs(regs, S, num_microbatches), None
    regs = list(regs)
    if len(regs) != S:
        raise ValueError(f"need {S} register quotas, got {len(regs)}")
    return regs, None


def _fold_precision_options(graph, optimizer: OptimizerSpec,
                            params: Dict[str, Any], *, zero, precision,
                            loss_scale) -> OptimizerSpec:
    """Resolve ``compile()``'s ``zero=``/``precision=``/``loss_scale=`` into
    the :class:`OptimizerSpec` fields the lowering and runtime layers read
    (``zero``/``zero_dp``/``zero_shapes``/``precision``). The spec's own
    ``__post_init__`` re-validates the folded result (zero requires AdamW;
    loss scaling requires bf16 compute over fp32 masters)."""
    import numpy as np

    if not zero and precision is None and loss_scale is None:
        return optimizer
    policy = precision
    if isinstance(policy, str):
        aliases = {"bf16": "bfloat16", "bfloat16": "bfloat16",
                   "fp32": "float32", "float32": "float32"}
        if policy not in aliases:
            raise ValueError(
                f"unknown precision {policy!r}; expected 'bf16'/'bfloat16', "
                "'fp32'/'float32', or a PrecisionPolicy")
        policy = PrecisionPolicy(compute_dtype=aliases[policy],
                                 loss_scale=loss_scale)
    elif isinstance(policy, PrecisionPolicy):
        if loss_scale is not None:
            policy = dataclasses.replace(policy, loss_scale=loss_scale)
    elif policy is not None:
        raise ValueError(
            f"precision= must be a dtype string or PrecisionPolicy, "
            f"got {type(policy).__name__}")
    elif loss_scale is not None:
        raise ValueError(
            "loss_scale= without precision= — loss scaling only exists to "
            "keep bf16 cotangents representable; pass precision='bf16' "
            "(fp32 compute never needs a scaled backward seed)")
    zero_dp, zero_shapes = 1, None
    if zero:
        pl = graph.placement
        sizes = dict(zip(pl.axis_names, pl.axis_sizes))
        if "data" in sizes:
            zero_dp = int(sizes["data"])
        elif len(pl.axis_names) == 1:
            # a sole placement axis doubles as the data axis
            zero_dp = int(pl.axis_sizes[0])
        else:
            raise ValueError(
                "zero=True requires a data axis to shard the optimizer "
                "state over: name one placement axis 'data' (placement "
                f"axes are {tuple(pl.axis_names)})")
        zero_shapes = tuple(
            (n, tuple(int(d) for d in np.shape(v)))
            for n, v in params.items())
    return dataclasses.replace(optimizer, zero=bool(zero), zero_dp=zero_dp,
                               zero_shapes=zero_shapes, precision=policy)


def _attach_static_report(sess, check: str):
    """Run the static plan verifier over a freshly compiled session
    (``check="static"``, the default) and attach the report for
    ``describe()``; a FAIL verdict closes the session's workers and raises
    :class:`repro.analysis.AnalysisError` naming the offending cycle/edge.
    ``check="off"`` records a SKIPPED report and returns immediately."""
    from repro import analysis

    if check == "off":
        sess.static_report = analysis.StaticReport(verdict="SKIPPED")
        return sess
    report = analysis.run_session_checks(sess)
    sess.static_report = report
    if report.verdict == "FAIL":
        sess.close()
        raise analysis.AnalysisError(report)
    return sess


def _apply_restore(sess: "Session", restore) -> "Session":
    """Resolve ``compile(restore=<snapshot dir>)``: load the newest completed
    snapshot and install it as the session's full training state — including
    the loss-scale trajectory when the snapshot recorded one."""
    if restore is None:
        return sess
    from repro.runtime.snapshot import load_snapshot

    params, opt_state, step, meta = load_snapshot(str(restore))
    sess.load_state(params=params, opt_state=opt_state, step=step)
    eng = sess._engine
    if (meta.get("loss_scale") is not None
            and getattr(eng, "loss_scale", None) is not None):
        eng.loss_scale = float(meta["loss_scale"])
        eng.scale_good_steps = int(meta.get("scale_good_steps", 0))
    return sess


def compile(graph, *, mode: str = "infer",
            backend: str = "actors", runtime: Optional[str] = None,
            plan: Optional[Plan] = None,
            partition: Optional[StagePartition] = None,
            stages: Optional[int] = None, num_microbatches: int = 1,
            microbatch_inputs: Optional[Sequence[str]] = None,
            regs=None, optimizer: Optional[OptimizerSpec] = None,
            params: Optional[Dict[str, Any]] = None, loss=None,
            lr: float = 1e-2, mesh=None, stage_meshes=None,
            fn_wrap=None, timeout: float = 300.0,
            snapshot_dir=None, snapshot_every: int = 1,
            restore=None, faults=None,
            zero: bool = False, precision=None, loss_scale=None,
            num_groups: Optional[int] = None,
            group_size: Optional[int] = None,
            cache_len: Optional[int] = None,
            max_prompt_len: Optional[int] = None,
            max_new_tokens: Optional[int] = None,
            cache: Optional[str] = None,
            page_len: Optional[int] = None,
            num_pages: Optional[int] = None,
            sampling=None,
            prefill_chunk: Optional[int] = None,
            check: str = "static"):
    """Compile a :class:`~repro.core.graph.LogicalGraph` into a runnable
    :class:`Session` — the single frontend over every lowering/executor path.

    ``mode="serve"`` instead compiles a
    :class:`repro.configs.base.ModelConfig` (or ``--arch`` name) into a
    :class:`ServeSession` running pipelined continuous-batching greedy
    decode: the stack is cut into ``stages`` model shards
    (:func:`repro.core.lowering.lower_serve_stages`), requests are packed
    into ``num_groups * group_size`` decode slots, and
    :meth:`ServeSession.generate` admits/retires requests mid-flight.
    Serve-only options: ``num_groups``, ``group_size``, ``cache_len``,
    ``max_prompt_len``, ``max_new_tokens``; ``params`` are the model params
    (default: ``build_model(...).init(PRNGKey(0))``), ``regs`` the
    per-stage quotas (list or policy), ``backend="monolithic"`` the
    whole-stack single-program reference. ``cache="paged"`` swaps the dense
    per-group cache blocks for the preallocated page pool of
    :mod:`repro.serve.paged_cache` (geometry via ``page_len=`` /
    ``num_pages=``, token-identical to dense), ``sampling=`` takes a
    :class:`repro.serve.sampler.SamplingSpec` (default: greedy), and
    ``prefill_chunk=`` (paged only) admits long prompts as bounded chunks
    interleaved with decode rounds.

    Declarative options (everything omitted is inferred):

    * ``mode``: ``"infer"`` (:meth:`Session.run`) or ``"train"``
      (:meth:`Session.step`; requires ``params``).
    * ``backend``: ``"actors"`` — per-stage jitted programs driven by stage
      actors with register-quota back-pressure (§4.3); ``"monolithic"`` —
      one whole-graph jitted program with identical microbatch semantics
      (the bit-identity reference).
    * ``runtime`` (actors backend only): ``"threads"`` (default) drives the
      actors on OS threads in this process; ``"processes"`` spawns one
      worker process per pipeline stage — stage state (placed params,
      optimizer state, serve caches) lives in the owning worker, payloads
      cross stages as serialized host arrays, and each worker re-lowers its
      stages from a picklable recipe (:mod:`repro.runtime.recipes`). With
      ``"processes"``, ``fn_wrap`` and a schedule-callable ``lr`` must be
      picklable (module-level, not lambdas/closures).
    * ``plan``: an SBP :class:`~repro.core.planner.Plan`; default
      :func:`repro.core.planner.plan` (Table-2 boxing-cost minimization).
    * ``partition`` / ``stages``: an explicit
      :class:`~repro.core.graph.StagePartition`, or a stage count for
      cost-balanced cutting; default: the graph's ``g.stage(k)``
      annotations. Actors backend only.
    * ``num_microbatches`` / ``microbatch_inputs``: how the batch streams
      through the pipeline. ``microbatch_inputs`` defaults to the non-param
      graph inputs in train mode; inference with ``num_microbatches > 1``
      must name them explicitly.
    * ``regs``: per-stage out-register quotas — an explicit list, a policy
      from :data:`REG_POLICIES` (``"1f1b"``, ``"gpipe"``, ``"serial"``), or
      None for compile-time resource planning via
      :func:`repro.runtime.pipeline.plan_registers` (§2.3).
    * ``optimizer``: an :class:`~repro.core.lowering.OptimizerSpec`
      (train mode only; default SGD at ``lr``).
    * ``params``: ``{graph input name: initial value}`` for every trainable
      input (train mode only); the session owns them across steps.
    * ``loss``: the sink to differentiate (default: the sole sink).
    * ``mesh`` / ``stage_meshes``: one shared device mesh (default
      ``graph.placement.to_mesh()``) or one mesh per stage — the paper's
      MPMD placement (actors backend only).
    * ``fn_wrap``: optional stage-body decorator (benchmarks use it to
      emulate device latency; actors backend only).
    * ``snapshot_dir`` / ``snapshot_every`` (train + actors only): write an
      async snapshot every N steps — one ``snap{s}`` actor per parameterized
      stage serializes that stage's post-update params + optimizer state off
      the schedule's hot path (:mod:`repro.runtime.snapshot`).
    * ``restore`` (train only): a ``snapshot_dir`` from an earlier session;
      the newest *completed* snapshot there becomes the session's initial
      params/optimizer state/step counter. Partition-agnostic — a snapshot
      taken on 4 stages restores onto 2 stages or the monolithic backend.
      ``params=`` is still required (shapes/ordering) but is overridden.
    * ``faults`` (train + actors only): a
      :class:`repro.runtime.chaos.FaultPlan` injected into the runtime —
      kill a named actor at its Nth fire, delay/duplicate a Req, drop an
      ack. The fault-tolerance tests drive kill-and-resume through this.
    * ``zero`` (train only): shard the optimizer's fp32 master params and
      AdamW moments across the placement's data axis as flat
      ``(dp, 1, chunk)`` tensors (§6.4, ZeRO-DP from SBP) — the opt actors'
      persistent register stream holds the shards; the forward sees gathered
      weights cast to the compute dtype (the Fig-14 ``cast`` placed *before*
      the gather, halving wire cost). Requires an AdamW optimizer and a data
      axis (an axis named ``"data"``, or a 1-d placement). Bit-identical to
      the dense path.
    * ``precision`` (train only): ``"bf16"``/``"bfloat16"`` runs
      forward/backward in bfloat16 over fp32 master params (cotangents and
      gradient accumulation stay fp32); ``"fp32"``/``"float32"`` is the
      default full-precision path; or pass a
      :class:`~repro.core.lowering.PrecisionPolicy` directly.
    * ``loss_scale`` (train only, requires ``precision="bf16"``): a float
      scales the loss backward seed statically (unscaled once after fp32
      accumulation — exact for powers of two); ``"dynamic"`` adds the
      ``scale`` actor riding the norm actor's stream: non-finite grad norms
      skip the update and back the scale off, sustained finite steps grow
      it.

    The monolithic backend accepts but does not use the schedule hints
    ``partition``/``stages``/``regs`` (so one kwargs dict can sweep both
    backends); ``stage_meshes`` and ``fn_wrap`` would change its execution
    and are rejected.

    ``check="static"`` (the default) runs the :mod:`repro.analysis` plan
    verifier over the compiled artifacts before returning — deadlock
    saturation of the actor network, SBP-legality of every edge, and the
    static per-device memory bound — and raises
    :class:`repro.analysis.AnalysisError` on a FAIL verdict (the offending
    cycle/edge is named; nothing has fired). ``check="off"`` skips it.
    """
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    if check not in ("static", "off"):
        raise ValueError(
            f"unknown check {check!r}; expected 'static' (run the "
            "repro.analysis plan verifier at compile time) or 'off'")
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if runtime is not None and runtime not in RUNTIME_KINDS:
        raise ValueError(
            f"unknown runtime {runtime!r}; expected one of {RUNTIME_KINDS}")
    if backend == "monolithic" and runtime is not None:
        raise ValueError(
            "runtime= requires backend='actors' (the monolithic backend "
            "runs one jitted program in-process, there is no actor runtime "
            "to choose)")
    if runtime is None and backend == "actors":
        runtime = "threads"
    if runtime == "processes":
        import jax

        if jax.default_backend() != "cpu":
            raise ValueError(
                "runtime='processes' runs one JAX client per pipeline node "
                "and needs the CPU backend; on "
                f"{jax.default_backend()!r} this process already holds the "
                "accelerator, which allows one process per chip — use "
                "runtime='threads'")
    # every mesh the lowering sees has Auto axes (repro.launch.mesh)
    mesh = as_auto(mesh)
    if stage_meshes is not None:
        stage_meshes = [as_auto(m) for m in stage_meshes]
    if mode != "train" and (zero or precision is not None
                            or loss_scale is not None):
        raise ValueError(
            "zero=/precision=/loss_scale= are only meaningful for "
            "mode='train' (they shape the optimizer's master/moment state "
            "and the backward seed; nothing is updated in other modes)")
    if mode != "train":
        train_only = {"snapshot_dir": snapshot_dir, "restore": restore,
                      "faults": faults}
        bad = [k for k, v in train_only.items() if v is not None]
        if bad or snapshot_every != 1:
            bad = bad or ["snapshot_every"]
            raise ValueError(
                f"{bad[0]}= is only meaningful for mode='train' "
                "(snapshots/restore/fault injection act on training state)")
    else:
        if backend != "actors":
            if snapshot_dir is not None:
                raise ValueError(
                    "snapshot_dir= requires backend='actors' (snapshots are "
                    "written by per-stage snap actors; checkpoint a "
                    "monolithic session with repro.train.checkpoint)")
            if faults is not None:
                raise ValueError(
                    "faults= requires backend='actors' (there are no "
                    "workers or messages to inject faults into)")
        if snapshot_every < 1:
            raise ValueError(
                f"snapshot_every must be >= 1, got {snapshot_every}")
        if snapshot_dir is None and snapshot_every != 1:
            raise ValueError("snapshot_every= without snapshot_dir=")
    if mode == "serve":
        rejected = {"plan": plan, "partition": partition,
                    "optimizer": optimizer, "loss": loss,
                    "microbatch_inputs": microbatch_inputs,
                    "stage_meshes": stage_meshes}
        bad = [k for k, v in rejected.items() if v is not None]
        if bad or num_microbatches != 1:
            bad = bad or ["num_microbatches"]
            raise ValueError(
                f"{bad[0]}= is not meaningful for mode='serve' (serving "
                "compiles a ModelConfig; schedule/optimizer options belong "
                "to graph modes)")
        return _compile_serve(
            graph, backend=backend, stages=stages, regs=regs, params=params,
            mesh=mesh, fn_wrap=fn_wrap, timeout=timeout,
            num_groups=num_groups, group_size=group_size,
            cache_len=cache_len, max_prompt_len=max_prompt_len,
            max_new_tokens=max_new_tokens, runtime=runtime, cache=cache,
            page_len=page_len, num_pages=num_pages, sampling=sampling,
            prefill_chunk=prefill_chunk, check=check)
    serve_only = {"num_groups": num_groups, "group_size": group_size,
                  "cache_len": cache_len, "max_prompt_len": max_prompt_len,
                  "max_new_tokens": max_new_tokens, "cache": cache,
                  "page_len": page_len, "num_pages": num_pages,
                  "sampling": sampling, "prefill_chunk": prefill_chunk}
    bad = [k for k, v in serve_only.items() if v is not None]
    if bad:
        raise ValueError(
            f"{bad[0]}= is only meaningful for mode='serve'")
    if num_microbatches < 1:
        raise ValueError(
            f"num_microbatches must be >= 1, got {num_microbatches}")
    if mode == "infer":
        if optimizer is not None:
            raise ValueError(
                "optimizer= is only meaningful for mode='train' "
                "(inference sessions never update params)")
        if params is not None:
            raise ValueError(
                "params= is only meaningful for mode='train'; inference "
                "sessions take every graph input at run() time")
        if loss is not None:
            raise ValueError(
                "loss= is only meaningful for mode='train' "
                "(nothing is differentiated in inference)")
    else:
        if params is None:
            raise ValueError(
                "mode='train' requires params= "
                "({graph input name: initial value})")
        params = _canonical_params(graph, params)
        if optimizer is None:
            optimizer = OptimizerSpec.sgd(lr)
        optimizer = _fold_precision_options(graph, optimizer, params,
                                            zero=zero, precision=precision,
                                            loss_scale=loss_scale)

    if plan is None:
        plan = plan_sbp(graph)

    input_names = [t.name for t in graph.inputs]
    if microbatch_inputs is None:
        if mode == "train":
            microbatch_inputs = [n for n in input_names if n not in params]
        elif num_microbatches > 1:
            raise ValueError(
                "num_microbatches > 1 needs microbatch_inputs= naming the "
                "graph inputs to split along axis 0")
        else:
            microbatch_inputs = []
    microbatch_inputs = list(microbatch_inputs)
    for n in microbatch_inputs:
        if n not in input_names:
            raise ValueError(f"{n} is not a graph input")

    if backend == "monolithic":
        # partition/stages/regs are schedule *hints* — harmless to accept so
        # a backend sweep can reuse one kwargs dict — but fn_wrap and
        # stage_meshes change execution and cannot be honored here
        if stage_meshes is not None:
            raise ValueError("stage_meshes requires backend='actors' "
                             "(the monolithic program runs on one mesh)")
        if fn_wrap is not None:
            raise ValueError("fn_wrap requires backend='actors' "
                             "(there are no stage bodies to wrap)")
        if mesh is None:
            mesh = graph.placement.to_mesh()
        if mode == "infer":
            engine = _MonolithicInferEngine(graph, plan, mesh,
                                            microbatch_inputs,
                                            num_microbatches)
        else:
            engine = _MonolithicTrainEngine(graph, plan, mesh, params,
                                            microbatch_inputs,
                                            num_microbatches, optimizer,
                                            loss=loss)
        sess = Session(graph=graph, mode=mode, backend=backend,
                       engine=engine, plan=plan, partition=None, regs=None,
                       reg_plan=None, optimizer=optimizer,
                       microbatch_inputs=microbatch_inputs,
                       num_microbatches=num_microbatches, timeout=timeout)
        sess = _attach_static_report(sess, check)
        return _apply_restore(sess, restore)

    part = _resolve_partition(graph, partition, stages)
    regs, reg_plan = _resolve_regs(regs, part, num_microbatches, mode)
    # the recipe captures the *user's* mesh choice (None -> each worker
    # defaults to graph.placement.to_mesh() itself, device-table agnostic)
    mesh_spec = MeshSpec.capture(mesh)
    stage_mesh_specs = (None if stage_meshes is None else
                        tuple(MeshSpec.capture(m) for m in stage_meshes))
    if mesh is None and stage_meshes is None:
        mesh = graph.placement.to_mesh()
    if mode == "infer":
        staged = lower_stages(graph, plan, part, mesh=mesh,
                              stage_meshes=stage_meshes)
        recipe = None
        if runtime == "processes":
            recipe = InferRecipe(graph, plan, part, mesh=mesh_spec,
                                 stage_meshes=stage_mesh_specs)
        engine = ActorPipelineExecutor(staged, microbatch_inputs,
                                       num_microbatches, regs=regs,
                                       fn_wrap=fn_wrap, runtime=runtime,
                                       recipe=recipe)
    else:
        tstaged = lower_train_stages(graph, plan, part, list(params),
                                     loss=loss, mesh=mesh,
                                     stage_meshes=stage_meshes,
                                     optimizer=optimizer)
        recipe = None
        if runtime == "processes":
            recipe = TrainRecipe(graph, plan, part, list(params), loss=loss,
                                 mesh=mesh_spec,
                                 stage_meshes=stage_mesh_specs,
                                 optimizer=optimizer)
        engine = TrainPipelineExecutor(tstaged, params, microbatch_inputs,
                                       num_microbatches, lr=lr, regs=regs,
                                       fn_wrap=fn_wrap, optimizer=optimizer,
                                       runtime=runtime, recipe=recipe,
                                       snapshot_dir=snapshot_dir,
                                       snapshot_every=snapshot_every,
                                       faults=faults)
    sess = Session(graph=graph, mode=mode, backend=backend, engine=engine,
                   plan=plan, partition=part, regs=regs, reg_plan=reg_plan,
                   optimizer=optimizer, microbatch_inputs=microbatch_inputs,
                   num_microbatches=num_microbatches, timeout=timeout,
                   runtime=runtime)
    sess = _attach_static_report(sess, check)
    return _apply_restore(sess, restore)


def _assert_tree_equal(name: str, a, b, context: str) -> None:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    if a.dtype != b.dtype or a.shape != b.shape or not np.array_equal(a, b):
        diff = ""
        if a.shape == b.shape and a.dtype == b.dtype:
            delta = np.max(np.abs(a.astype(np.float64)
                                  - b.astype(np.float64)))
            diff = f" (max abs diff {delta:g})"
        raise AssertionError(
            f"sessions disagree on {name} at {context}: "
            f"{a.dtype}{list(a.shape)} vs {b.dtype}{list(b.shape)}{diff}")


def assert_sessions_match(a: Session, b: Session, inputs: Dict[str, Any],
                          steps: int = 1) -> None:
    """Bit-identity check between two sessions compiled from the same graph
    (typically ``backend="actors"`` vs ``backend="monolithic"``).

    Inference sessions: run both on ``inputs`` and compare every sink
    bitwise. Training sessions: step both ``steps`` times on the same batch
    and compare loss, post-clip grads, updated params, and (when stateful)
    the merged optimizer state after every step. Raises ``AssertionError``
    naming the first mismatching tensor.
    """
    if a.mode != b.mode:
        raise ValueError(f"cannot compare mode={a.mode!r} with {b.mode!r}")
    if a.mode == "infer":
        ra, rb = a.run(**inputs), b.run(**inputs)
        for name in ra:
            _assert_tree_equal(f"sink {name!r}", ra[name], rb[name], "run")
        return
    import numpy as np

    for k in range(steps):
        sa, sb = a.step(**inputs), b.step(**inputs)
        ctx = f"step {k}"
        _assert_tree_equal("loss", sa.loss, sb.loss, ctx)
        for n in sa.grads:
            _assert_tree_equal(f"grad {n!r}", sa.grads[n], sb.grads[n], ctx)
        for n in sa.params:
            _assert_tree_equal(f"param {n!r}", sa.params[n], sb.params[n],
                               ctx)
        oa, ob = a.opt_state, b.opt_state
        if (oa is None) != (ob is None):
            raise AssertionError(
                f"sessions disagree on opt_state presence at {ctx}")
        if oa is not None:
            if int(oa.step) != int(ob.step):
                raise AssertionError(
                    f"opt_state.step differs at {ctx}: "
                    f"{int(oa.step)} vs {int(ob.step)}")
            for n in oa.mu:
                _assert_tree_equal(f"opt mu {n!r}", oa.mu[n], ob.mu[n], ctx)
                _assert_tree_equal(f"opt nu {n!r}", oa.nu[n], ob.nu[n], ctx)
