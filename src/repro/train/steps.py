"""SPMD train / serve step builders.

These wrap the (local-shard) model functions in ``shard_map`` over the
production mesh with explicit in/out shardings — the "physical graph" of the
paper, with every collective visible in the lowered HLO (which is what the
roofline analysis parses).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.configs.base import ModelConfig
from repro.models.common import MeshPlan
from repro.models.model_zoo import build_model, cache_specs, make_decode_caches
from repro.optim.adamw import AdamWConfig, AdamWState
from repro.optim.zero import (
    combine_model_grads, gather_master_local, init_zero_state_local,
    local_shape_of, master_specs, model_combine_tree, plain_dp_adamw_update,
    shard_master_local, zero_adamw_update, zero_state_specs)


def plan_from_mesh(mesh) -> MeshPlan:
    return MeshPlan(tuple(mesh.axis_names), tuple(mesh.devices.shape))


def _dp_spec(plan: MeshPlan):
    axes = plan.data_axes
    return axes if len(axes) > 1 else axes[0]


def batch_specs(cfg: ModelConfig, plan: MeshPlan, kind: str):
    """PartitionSpecs for a batch dict (global arrays)."""
    dp = _dp_spec(plan)
    if kind == "train":
        if cfg.embed_frontend and not cfg.encoder_decoder:
            sp = {"embeds": P(dp), "labels": P(dp)}
        else:
            sp = {"tokens": P(dp)}
        if cfg.encoder_decoder:
            sp["enc_embeds"] = P(dp)
        return sp
    if kind == "prefill":
        if cfg.embed_frontend and not cfg.encoder_decoder:
            sp = {"embeds": P(dp)}
        else:
            sp = {"tokens": P(dp)}
        if cfg.encoder_decoder:
            sp["enc_embeds"] = P(dp)
        return sp
    raise ValueError(kind)


def _replication_tree(specs, plan: MeshPlan):
    """Per-leaf count of identical model-axis copies (for grad-norm math)."""
    mx = plan.model_axis

    def leaf(spec):
        flat = []
        for entry in spec:
            if isinstance(entry, tuple):
                flat.extend(entry)
            elif entry is not None:
                flat.append(entry)
        return 1 if mx in flat else plan.tp

    return jax.tree.map(leaf, specs, is_leaf=lambda s: isinstance(s, P))


# Model-replicated params whose per-device gradient contributions are
# DISJOINT (each device computes grads only through its kv-head / expert /
# B,C-group slice): these need a psum over the model axis before the update.
# Replicated params with IDENTICAL per-device grads (layer norms, wkv_a, ...)
# need none. Distinguished by leaf name.
_MODEL_GRAD_SUM_LEAVES = frozenset(
    {"wk", "wv", "bk", "bv", "q_norm", "k_norm", "w_bc", "conv_bc", "router"})


def _grad_sync_tree(specs, plan: MeshPlan):
    mx = plan.model_axis

    def mode(path, spec):
        flat = []
        for entry in spec:
            if isinstance(entry, tuple):
                flat.extend(entry)
            elif entry is not None:
                flat.append(entry)
        if mx in flat:
            return "none"                      # sharded: local grad is exact
        name = None
        for p in reversed(path):
            name = getattr(p, "key", None)
            if name is not None:
                break
        return "sum" if name in _MODEL_GRAD_SUM_LEAVES else "none"

    import jax.tree_util as jtu
    return jtu.tree_map_with_path(mode, specs,
                                  is_leaf=lambda s: isinstance(s, P))


def _sync_model_grads(grads, sync_tree, plan: MeshPlan):
    if plan.tp == 1:
        return grads

    def fix(g, mode):
        return jax.lax.psum(g, plan.model_axis) if mode == "sum" else g

    return jax.tree.map(fix, grads, sync_tree)


@dataclasses.dataclass
class TrainStep:
    step_fn: Any            # jitted: (params, opt_state, batch) -> (params, opt, metrics)
    param_specs: Any        # specs of the step's param argument (masters if zero)
    model_param_specs: Any  # specs of the unflattened model params
    opt_specs: Any
    batch_specs: Dict
    init_params: Any        # (key) -> global model params (small runs only)
    init_opt: Any           # (step-params) -> opt state (jitted, sharded)
    plan: MeshPlan
    zero: bool = True
    shard_params_fn: Any = None   # full model params -> flat masters (zero)
    gather_params_fn: Any = None  # flat masters -> full model params (zero)


def make_train_step(cfg: ModelConfig, mesh, optimizer: AdamWConfig = None,
                    zero: bool = True, remat: bool = True,
                    fsdp: bool = False) -> TrainStep:
    """``fsdp=True``: beyond-paper plan for small models — the model axis
    becomes extra data parallelism (pure ZeRO/FSDP over all 256/512 chips);
    the per-layer tensor-parallel boxing collectives disappear entirely."""
    optimizer = optimizer or AdamWConfig()
    plan = plan_from_mesh(mesh)
    if fsdp:
        plan = MeshPlan(plan.axis_names, plan.axis_sizes,
                        model_axis="__fsdp_none__")
    bundle = build_model(cfg, plan)
    pspecs = bundle.specs()
    bspecs = batch_specs(cfg, plan, "train")
    repl = _replication_tree(pspecs, plan)
    def is_spec(s):
        return isinstance(s, P)
    cdt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    def certified_mean(v):
        vma = jax.typeof(v).vma
        missing = tuple(n for n in plan.axis_names if n not in vma)
        if missing:
            v = jax.lax.pcast(v, missing, to="varying")
        return jax.lax.pmean(v, plan.axis_names)

    metric_names = {"lm_loss": 0, "aux_loss": 0, "loss": 0,
                    **({"mtp_loss": 0} if cfg.mtp else {}), "grad_norm": 0}
    mspecs_out = jax.tree.map(lambda *_: P(), metric_names)

    if zero:
        # ---- FSDP/ZeRO path: flat (DP, TP, chunk) master shards -------------
        arg_specs = master_specs(pspecs, plan)
        ospecs = zero_state_specs(pspecs, plan)
        combine = model_combine_tree(pspecs, plan)
        params_global_s = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
        local_shapes = jax.tree.map(
            lambda sds, spec: local_shape_of(sds.shape, spec, plan),
            params_global_s, pspecs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

        def gather_full_(masters):
            import jax.tree_util as jtu
            flat_m, treedef = jtu.tree_flatten(masters)
            flat_s = treedef.flatten_up_to(local_shapes)
            return treedef.unflatten([
                gather_master_local(m, tuple(s), cdt, plan)
                for m, s in zip(flat_m, flat_s)])

        def local_step(masters, opt_state, batch):
            def loss_fn(mf):
                return bundle.loss_fn(gather_full_(mf), batch)

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(masters)
            # AD's all_gather transpose already reduce-scattered over data;
            # normalize the data-sum to a mean, then combine over model.
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / plan.dp, grads)
            grads = combine_model_grads(grads, combine, plan)
            new_m, new_opt, gnorm = zero_adamw_update(
                optimizer, masters, grads, opt_state, plan, repl)
            metrics["grad_norm"] = gnorm
            metrics = {k: certified_mean(v) for k, v in metrics.items()}
            return new_m, new_opt, metrics

        step_fn = jax.jit(
            shard_map(local_step, mesh=mesh,
                      in_specs=(arg_specs, ospecs, bspecs),
                      out_specs=(arg_specs, ospecs, mspecs_out),
                      check=True),
            donate_argnums=(0, 1))

        def init_opt(masters):
            fn = jax.jit(shard_map(
                lambda m: init_zero_state_local(m, plan), mesh=mesh,
                in_specs=(arg_specs,), out_specs=ospecs, check=False))
            return fn(masters)

        shard_params_fn = jax.jit(shard_map(
            lambda p: jax.tree.map(
                lambda l: shard_master_local(l, plan), p),
            mesh=mesh, in_specs=(pspecs,), out_specs=arg_specs,
            check=False))
        gather_params_fn = jax.jit(shard_map(
            gather_full_, mesh=mesh, in_specs=(arg_specs,),
            out_specs=pspecs, check=False))

        return TrainStep(step_fn, arg_specs, pspecs, ospecs, bspecs,
                         bundle.init, init_opt, plan, zero=True,
                         shard_params_fn=shard_params_fn,
                         gather_params_fn=gather_params_fn)

    # ---- plain data-parallel baseline (§6.2) --------------------------------
    ospecs = AdamWState(P(), jax.tree.map(lambda s: s, pspecs, is_leaf=is_spec),
                        jax.tree.map(lambda s: s, pspecs, is_leaf=is_spec))

    def local_step(params, opt_state, batch):
        def loss_fn(p):
            return bundle.loss_fn(p, batch)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_params, new_opt, gnorm = plain_dp_adamw_update(
            optimizer, params, grads, opt_state, plan, repl)
        metrics["grad_norm"] = gnorm
        metrics = {k: certified_mean(v) for k, v in metrics.items()}
        return new_params, new_opt, metrics

    step_fn = jax.jit(
        shard_map(local_step, mesh=mesh,
                  in_specs=(pspecs, ospecs, bspecs),
                  out_specs=(pspecs, ospecs, mspecs_out),
                  check=True),
        donate_argnums=(0, 1))

    def init_opt(params):
        from repro.optim.adamw import init_adamw
        fn = jax.jit(shard_map(init_adamw, mesh=mesh, in_specs=(pspecs,),
                               out_specs=ospecs, check=False))
        return fn(params)

    return TrainStep(step_fn, pspecs, pspecs, ospecs, bspecs, bundle.init,
                     init_opt, plan, zero=False)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeStep:
    prefill_fn: Any
    decode_fn: Any
    init_caches_fn: Any
    logits_fn: Any          # (params, h_last) -> logits, the decode head
    param_specs: Any
    cache_specs_: Any
    batch_specs: Dict
    plan: MeshPlan


def greedy_from_logits(logits, vocab_size: int):
    """Greedy token selection over a padded vocabulary.

    The unembedding is padded to ``cfg.padded_vocab()`` columns, so a bare
    argmax can emit padding ids >= ``vocab_size`` (junk the tokenizer cannot
    decode). Mask the padding columns to -inf first; the result is always a
    valid id < ``vocab_size``.
    """
    logits = jnp.asarray(logits)
    mask = jnp.arange(logits.shape[-1]) >= vocab_size
    return jnp.argmax(jnp.where(mask, -jnp.inf, logits),
                      axis=-1).astype(jnp.int32)


def make_serve_step(cfg: ModelConfig, mesh, cache_len: int,
                    sliding_window: int = 0, ring: bool = False,
                    shard_batch: bool = True) -> ServeStep:
    """``ring=True``: sliding-window ring-buffer cache (cache_len == window).
    ``shard_batch=False``: global batch < dp (long_500k) — batch replicated
    over the data axes, KV cache sharded over the model axis only."""
    plan = plan_from_mesh(mesh)
    bundle = build_model(cfg, plan, sliding_window=sliding_window)
    pspecs = bundle.specs()
    bspecs = batch_specs(cfg, plan, "prefill")
    batch_axes = plan.data_axes if shard_batch else ()
    cspecs = cache_specs(cfg, plan, batch_axes, ring=ring)
    dp = _dp_spec(plan) if shard_batch else None
    if not shard_batch:
        bspecs = jax.tree.map(lambda _: P(), bspecs,
                              is_leaf=lambda s: isinstance(s, P))

    def local_prefill(params, batch):
        return bundle.prefill(params, batch, cache_len)

    prefill_fn = jax.jit(
        shard_map(local_prefill, mesh=mesh, in_specs=(pspecs, bspecs),
                  out_specs=(P(dp), cspecs), check=False))

    def local_decode(params, caches, tok, pos):
        return bundle.decode_step(params, caches, tok, pos)

    decode_fn = jax.jit(
        shard_map(local_decode, mesh=mesh,
                  in_specs=(pspecs, cspecs, P(dp), P(dp)),
                  out_specs=(P(dp, plan.model_axis), cspecs),
                  check=False),
        donate_argnums=(1,))

    def local_init_caches(tok):
        B_l = tok.shape[0]
        return make_decode_caches(cfg, plan, B_l, cache_len, ring=ring)

    init_caches_fn = jax.jit(
        shard_map(local_init_caches, mesh=mesh, in_specs=(P(dp),),
                  out_specs=cspecs, check=False))

    def local_logits(params, h_last):
        # the decode-step head, bit for bit (decode_step's final matmul):
        # prefill's first-token logits must come from THIS program, not a
        # host-side h @ unembed that skips the shard_map and promotes dtypes
        return h_last[:, 0] @ params["unembed"].astype(h_last.dtype)

    logits_fn = jax.jit(
        shard_map(local_logits, mesh=mesh, in_specs=(pspecs, P(dp)),
                  out_specs=P(dp, plan.model_axis), check=False))

    return ServeStep(prefill_fn, decode_fn, init_caches_fn, logits_fn,
                     pspecs, cspecs, bspecs, plan)


# ---------------------------------------------------------------------------
# LogicalGraph training steps — DEPRECATED shims over repro.api.compile.
#
# The real machinery lives in repro.api: compile(graph, mode="train",
# backend="monolithic"|"actors") returns a Session with one uniform surface.
# These wrappers only preserve the historical calling conventions
# (per-call param threading for the monolithic step, a bare
# TrainPipelineExecutor for the pipelined one) for code written against
# PR 2/3; new code should call repro.api.compile directly.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GraphTrainStep:
    """Monolithic microbatched SPMD training step over a ``LogicalGraph``.

    ``step_fn(param_values, data) -> (loss, grads, new_params)``: runs every
    microbatch through one whole-graph jitted value-and-grad program,
    accumulates gradients in fp32, and applies the
    :class:`repro.core.lowering.OptimizerSpec` (default plain SGD) — with
    global-norm clipping and the lr schedule resolved exactly like the
    pipeline's optimizer actors, via the same
    :mod:`repro.optim.adamw` kernels in the same canonical param order. The
    objective is the sum of the loss sink over the whole batch; ``grads``
    are post-clip when clipping is on. This is the reference
    :func:`make_pipeline_train_step` is checked against, bit for bit.

    A stateful optimizer's :class:`repro.optim.adamw.AdamWState` persists on
    ``opt_state`` across :meth:`step` calls; ``step_count`` indexes the lr
    schedule; ``last_grad_norm`` is the pre-clip global norm (None when
    clipping is off).
    """

    step_fn: Any
    param_names: Tuple[str, ...]
    num_microbatches: int
    lr: float
    optimizer: Any = None
    opt_state: Any = None
    step_count: int = 0
    last_grad_norm: Any = None

    def step(self, param_values: Dict[str, Any], data: Dict[str, Any]):
        return self.step_fn(param_values, data)


def make_graph_train_step(graph, mesh, params, microbatch_inputs,
                          num_microbatches: int, lr: float = 1e-2,
                          loss=None, graph_plan=None,
                          optimizer=None) -> GraphTrainStep:
    """DEPRECATED: use ``repro.api.compile(graph, mode="train",
    backend="monolithic", ...)`` — this shim only adapts the old
    params-threaded-per-call convention onto the session it builds.

    ``params`` names the graph inputs to train; ``microbatch_inputs`` names
    the inputs split along axis 0 into ``num_microbatches`` chunks. The SBP
    plan is computed with :func:`repro.core.planner.plan` unless
    ``graph_plan`` is given. ``optimizer`` is an
    :class:`repro.core.lowering.OptimizerSpec` (default: SGD at ``lr``).
    """
    import warnings

    warnings.warn(
        "make_graph_train_step is deprecated; use repro.api.compile("
        "graph, mode='train', backend='monolithic', ...) instead",
        DeprecationWarning, stacklevel=2)

    from repro import api
    from repro.core.lowering import (OptimizerSpec, _resolve_loss,
                                     _resolve_params)

    param_names = tuple(getattr(t, "name", t) for t in params)
    # fail at build time like the old direct lowering did, not on first step
    _resolve_params(graph, param_names)
    _resolve_loss(graph, loss)
    opt = optimizer if optimizer is not None else OptimizerSpec.sgd(lr)
    ts = GraphTrainStep(step_fn=None, param_names=param_names,
                        num_microbatches=num_microbatches, lr=lr,
                        optimizer=opt)
    holder: Dict[str, Any] = {"session": None}

    def step_fn(param_values: Dict[str, Any], data: Dict[str, Any]):
        sess = holder["session"]
        missing = [n for n in param_names if n not in param_values]
        if missing:
            raise ValueError(f"missing params: {missing}")
        pvals = {n: param_values[n] for n in param_names}
        if sess is None:
            sess = holder["session"] = api.compile(
                graph, mode="train", backend="monolithic", plan=graph_plan,
                mesh=mesh, params=pvals,
                microbatch_inputs=list(microbatch_inputs),
                num_microbatches=num_microbatches, lr=lr, optimizer=opt,
                loss=loss)
        else:
            sess.load_params(pvals)
        res = sess.step(**{n: v for n, v in data.items()
                           if n not in pvals})
        ts.opt_state = sess.opt_state
        ts.step_count = sess.step_count
        ts.last_grad_norm = res.metrics["grad_norm"]
        return res.loss, res.grads, res.params

    ts.step_fn = step_fn
    return ts


def make_pipeline_train_step(graph, init_params: Dict[str, Any],
                             microbatch_inputs, num_microbatches: int,
                             num_stages: Optional[int] = None, mesh=None,
                             stage_meshes=None, lr: float = 1e-2,
                             regs=None, loss=None, graph_plan=None,
                             optimizer=None):
    """DEPRECATED: use ``repro.api.compile(graph, mode="train",
    backend="actors", ...)`` — this shim compiles a session and returns its
    backing :class:`repro.runtime.pipeline.TrainPipelineExecutor` to
    preserve the historical return type.

    ``init_params`` maps each trainable graph input to its initial value;
    the executor owns the params (and any optimizer state) from then on.
    ``optimizer`` is an :class:`repro.core.lowering.OptimizerSpec` —
    AdamW runs with per-stage state actors and, with ``grad_clip`` > 0, a
    cross-stage ``norm`` actor for global-norm clipping (default: SGD at
    ``lr``).
    """
    import warnings

    warnings.warn(
        "make_pipeline_train_step is deprecated; use repro.api.compile("
        "graph, mode='train', backend='actors', ...) instead",
        DeprecationWarning, stacklevel=2)

    from repro import api

    sess = api.compile(
        graph, mode="train", backend="actors", plan=graph_plan,
        stages=num_stages, params=init_params,
        microbatch_inputs=list(microbatch_inputs),
        num_microbatches=num_microbatches, lr=lr,
        # preserve this shim's historical default schedule (1F1B) rather
        # than compile()'s simulated register planning
        regs=regs if regs is not None else "1f1b",
        loss=loss, mesh=mesh, stage_meshes=stage_meshes,
        optimizer=optimizer)
    return sess.executor
