"""The `repro.api` Session frontend (paper §2/§4: one compile step).

Acceptance criteria of the api_redesign tentpole, pinned down:

(a) one import drives all four modes: train/infer x actors/monolithic all
    produce Sessions whose outputs/losses/grads/params/opt-state are
    bit-identical across backends on a shared 4-stage graph;
(b) omitted declarative options (plan / partition / regs /
    microbatch_inputs) infer values that reproduce the explicit-argument
    results exactly;
(c) invalid combinations fail fast with a clear ValueError naming the
    offending option or key — including unknown/missing run()/step() input
    names on both the Sessions and the underlying executors;
(d) the historical entry points (`make_graph_train_step`,
    `make_pipeline_train_step`) are deprecated shims over `api.compile`
    with unchanged numerics.
"""
import numpy as np
import pytest

from repro import api
from repro.core.graph import LogicalGraph, partition_stages
from repro.core.lowering import OptimizerSpec
from repro.core.placement import Placement
from repro.core.planner import plan as plan_sbp

B, W, S, M = 16, 32, 4, 4


def _graph(batch=B, width=W, depth=S, with_loss=True):
    placement = Placement(("d",), (1,), device_kind="cpu")
    g = LogicalGraph(placement)
    h = g.input("x", (batch, width))
    if with_loss:
        labels = g.input("labels", (batch,), dtype="int32")
    for i in range(depth):
        w = g.input(f"w{i}", (width, width))
        h = g.matmul(h, w, name=f"mm{i}")
        if i < depth - 1:
            h = g.unary(h, "relu", name=f"relu{i}")
    if with_loss:
        g.softmax_xent(h, labels, name="loss")
    return g


def _params_and_data(g, seed=0):
    rng = np.random.default_rng(seed)
    params, data = {}, {}
    for t in g.inputs:
        if t.name.startswith("w"):
            params[t.name] = (rng.normal(size=t.shape) * 0.1).astype(np.float32)
        elif t.dtype == "int32":
            data[t.name] = rng.integers(0, W, size=t.shape).astype(np.int32)
        else:
            data[t.name] = rng.normal(size=t.shape).astype(np.float32)
    return params, data


class TestFourWayBitIdentity:
    def test_infer_actors_vs_monolithic(self):
        g = _graph(with_loss=False)
        params, data = _params_and_data(g)
        inputs = {**params, **data}
        pipe = api.compile(g, mode="infer", backend="actors", stages=S,
                           num_microbatches=M, microbatch_inputs=["x"])
        mono = api.compile(g, mode="infer", backend="monolithic",
                           num_microbatches=M, microbatch_inputs=["x"])
        api.assert_sessions_match(pipe, mono, inputs)
        # and the sinks are named
        out = pipe.run(**inputs)
        assert set(out) == {"mm3.out"}
        assert out["mm3.out"].shape == (B, W)

    def test_train_sgd_actors_vs_monolithic_multi_step(self):
        g = _graph()
        params, data = _params_and_data(g)
        pipe = api.compile(g, mode="train", backend="actors", stages=S,
                           params=dict(params), num_microbatches=M)
        mono = api.compile(g, mode="train", backend="monolithic",
                           params=dict(params), num_microbatches=M)
        api.assert_sessions_match(pipe, mono, data, steps=3)
        assert pipe.step_count == mono.step_count == 3
        assert pipe.opt_state is None and mono.opt_state is None

    def test_train_adamw_clip_schedule_actors_vs_monolithic(self):
        g = _graph()
        params, data = _params_and_data(g)
        opt = OptimizerSpec.adamw(lr=lambda s: 1e-3 * 0.8 ** s,
                                  grad_clip=1.0)
        pipe = api.compile(g, mode="train", backend="actors", stages=S,
                           params=dict(params), num_microbatches=M,
                           optimizer=opt)
        mono = api.compile(g, mode="train", backend="monolithic",
                           params=dict(params), num_microbatches=M,
                           optimizer=opt)
        api.assert_sessions_match(pipe, mono, data, steps=3)
        assert int(pipe.opt_state.step) == 3
        assert pipe.history[-1]["lr"] == pytest.approx(1e-3 * 0.8 ** 2)

    def test_mismatch_is_detected(self):
        """assert_sessions_match must actually fail on different numbers."""
        g = _graph()
        params, data = _params_and_data(g)
        p2 = {n: v + 1.0 for n, v in params.items()}
        a = api.compile(g, mode="train", backend="actors", stages=S,
                        params=params, num_microbatches=M)
        b = api.compile(g, mode="train", backend="monolithic",
                        params=p2, num_microbatches=M)
        with pytest.raises(AssertionError, match="disagree"):
            api.assert_sessions_match(a, b, data)


class TestOptionInference:
    def test_omitted_plan_partition_regs_match_explicit(self):
        g = _graph()
        params, data = _params_and_data(g)
        auto = api.compile(g, mode="train", stages=S, params=dict(params),
                           num_microbatches=M)
        explicit = api.compile(
            g, mode="train", params=dict(params), num_microbatches=M,
            plan=plan_sbp(g), partition=partition_stages(g, S),
            regs=list(auto.regs), microbatch_inputs=["x", "labels"],
            mesh=g.placement.to_mesh())
        assert auto.partition.stage_of == explicit.partition.stage_of
        assert auto.regs == explicit.regs
        assert auto.microbatch_inputs == ["x", "labels"]
        api.assert_sessions_match(auto, explicit, data, steps=2)

    def test_auto_regs_come_from_register_planning(self):
        g = _graph()
        params, _ = _params_and_data(g)
        sess = api.compile(g, mode="train", stages=S, params=dict(params),
                           num_microbatches=8)
        assert sess.reg_plan is not None
        assert sess.regs == sess.reg_plan.regs
        assert all(r >= 1 for r in sess.regs)

    def test_reg_policies(self):
        g = _graph()
        params, data = _params_and_data(g)
        for policy, want in (("1f1b", [S - s for s in range(S)]),
                             ("gpipe", [M] * S), ("serial", [1] * S)):
            sess = api.compile(g, mode="train", stages=S, params=dict(params),
                               num_microbatches=M, regs=policy)
            assert sess.regs == want, policy
        with pytest.raises(ValueError, match="regs policy"):
            api.compile(g, mode="train", stages=S, params=dict(params),
                        num_microbatches=M, regs="zigzag")

    def test_stage_annotations_drive_default_partition(self):
        placement = Placement(("d",), (1,), device_kind="cpu")
        g = LogicalGraph(placement)
        x = g.input("x", (8, 16))
        labels = g.input("labels", (8,), dtype="int32")
        w0, w1 = g.input("w0", (16, 16)), g.input("w1", (16, 16))
        with g.stage(0):
            h = g.unary(g.matmul(x, w0, name="mm0"), "relu", name="r0")
        with g.stage(1):
            g.softmax_xent(g.matmul(h, w1, name="mm1"), labels, name="loss")
        sess = api.compile(g, mode="infer", backend="actors")
        assert sess.partition.num_stages == 2

    def test_graph_compile_sugar(self):
        g = _graph(with_loss=False)
        params, data = _params_and_data(g)
        sess = g.compile(mode="infer", backend="monolithic")
        out = sess.run(**params, **data)
        assert set(out) == {"mm3.out"}

    def test_describe_reports_plan_partition_quotas(self):
        g = _graph()
        params, _ = _params_and_data(g)
        sess = api.compile(g, mode="train", stages=S, params=dict(params),
                           num_microbatches=M, regs="1f1b")
        rep = sess.describe()
        assert "stage partition" in rep and "SBP plan" in rep
        assert "regs=4" in rep and "regs=1" in rep      # 1F1B quotas S-s
        assert "optimizer: sgd" in rep
        mono = api.compile(g, mode="train", backend="monolithic",
                           params=dict(params), num_microbatches=M)
        assert "no stage partition" in mono.describe()


class TestCompileValidation:
    def test_infer_with_optimizer_raises(self):
        g = _graph()
        with pytest.raises(ValueError, match="optimizer"):
            api.compile(g, mode="infer", optimizer=OptimizerSpec.sgd())

    def test_infer_with_params_raises(self):
        g = _graph()
        params, _ = _params_and_data(g)
        with pytest.raises(ValueError, match="params"):
            api.compile(g, mode="infer", params=params)

    def test_infer_with_loss_raises(self):
        with pytest.raises(ValueError, match="loss"):
            api.compile(_graph(), mode="infer", loss="loss.out")

    def test_train_without_params_raises(self):
        with pytest.raises(ValueError, match="params"):
            api.compile(_graph(), mode="train")

    def test_unknown_mode_backend_raise(self):
        g = _graph()
        with pytest.raises(ValueError, match="mode"):
            api.compile(g, mode="serve")
        with pytest.raises(ValueError, match="backend"):
            api.compile(g, mode="infer", backend="xla")

    def test_params_not_graph_inputs_raise(self):
        g = _graph()
        params, _ = _params_and_data(g)
        params["w_typo"] = params["w0"]
        with pytest.raises(ValueError, match="w_typo"):
            api.compile(g, mode="train", params=params)

    def test_partition_stages_contradiction_raises(self):
        g = _graph()
        params, _ = _params_and_data(g)
        with pytest.raises(ValueError, match="contradicts"):
            api.compile(g, mode="train", params=dict(params),
                        partition=partition_stages(g, 4), stages=2)

    def test_microbatched_infer_needs_names(self):
        g = _graph(with_loss=False)
        with pytest.raises(ValueError, match="microbatch_inputs"):
            api.compile(g, mode="infer", num_microbatches=4)

    def test_monolithic_rejects_stage_meshes(self):
        g = _graph(with_loss=False)
        with pytest.raises(ValueError, match="stage_meshes"):
            api.compile(g, mode="infer", backend="monolithic",
                        stage_meshes=[g.placement.to_mesh()])

    def test_monolithic_rejects_fn_wrap_but_accepts_schedule_hints(self):
        g = _graph(with_loss=False)
        with pytest.raises(ValueError, match="fn_wrap"):
            api.compile(g, mode="infer", backend="monolithic",
                        fn_wrap=lambda s, f: f)
        # schedule hints are accepted so one kwargs dict can sweep backends
        sess = api.compile(g, mode="infer", backend="monolithic",
                           stages=S, regs="1f1b")
        assert sess.partition is None and sess.regs is None

    @pytest.mark.parametrize("mode", ["infer", "train"])
    def test_graph_modes_reject_fn_wrap(self, mode):
        """fn_wrap wraps serve stage bodies only: a graph session on
        either backend names it in its refusal."""
        g = _graph(with_loss=mode == "train")
        kw = {}
        if mode == "train":
            kw["params"] = dict(_params_and_data(g)[0])
        for backend in ("actors", "monolithic"):
            with pytest.raises(ValueError, match="fn_wrap"):
                api.compile(g, mode=mode, backend=backend, stages=S,
                            fn_wrap=lambda s, f: f, **kw)

    def test_run_step_mode_mismatch(self):
        g = _graph()
        params, data = _params_and_data(g)
        train = api.compile(g, mode="train", stages=S, params=dict(params),
                            num_microbatches=M)
        infer = api.compile(_graph(with_loss=False), mode="infer",
                            backend="monolithic")
        with pytest.raises(RuntimeError, match="step"):
            train.run(**data)
        with pytest.raises(RuntimeError, match="run"):
            infer.step(x=data["x"])


class TestInputNameValidation:
    """Satellite: unknown/missing run/step inputs raise a ValueError naming
    the offending key instead of failing deep in actor bodies."""

    def _sessions(self):
        g = _graph()
        params, data = _params_and_data(g)
        pipe = api.compile(g, mode="train", backend="actors", stages=S,
                           params=dict(params), num_microbatches=M)
        mono = api.compile(g, mode="train", backend="monolithic",
                           params=dict(params), num_microbatches=M)
        return params, data, pipe, mono

    @pytest.mark.parametrize("backend", ["actors", "monolithic"])
    def test_step_unknown_and_missing_inputs(self, backend):
        params, data, pipe, mono = self._sessions()
        sess = pipe if backend == "actors" else mono
        with pytest.raises(ValueError, match="'junk'"):
            sess.step(**data, junk=data["x"])
        with pytest.raises(ValueError, match="'labels'"):
            sess.step(x=data["x"])

    @pytest.mark.parametrize("backend", ["actors", "monolithic"])
    def test_step_rejects_param_passed_as_data(self, backend):
        params, data, pipe, mono = self._sessions()
        sess = pipe if backend == "actors" else mono
        with pytest.raises(ValueError, match="'w0'.*owned by the executor"):
            sess.step(**data, w0=params["w0"])

    @pytest.mark.parametrize("backend", ["actors", "monolithic"])
    def test_infer_run_unknown_and_missing_inputs(self, backend):
        g = _graph(with_loss=False)
        params, data = _params_and_data(g)
        sess = api.compile(g, mode="infer", backend=backend,
                           **({"stages": S} if backend == "actors" else {}),
                           num_microbatches=M, microbatch_inputs=["x"])
        with pytest.raises(ValueError, match="'w9'"):
            sess.run(**params, **data, w9=params["w0"])
        with pytest.raises(ValueError, match="'x'"):
            sess.run(**params)

    def test_executors_validate_directly(self):
        """The underlying executors raise the same errors without a Session
        in front of them."""
        from repro.core.lowering import lower_stages, lower_train_stages
        from repro.runtime import (ActorPipelineExecutor,
                                   TrainPipelineExecutor)

        g = _graph(with_loss=False)
        params, data = _params_and_data(g)
        p = plan_sbp(g)
        part = partition_stages(g, S)
        mesh = g.placement.to_mesh()
        ex = ActorPipelineExecutor(lower_stages(g, p, part, mesh=mesh),
                                   ["x"], num_microbatches=M)
        with pytest.raises(ValueError, match="'bogus'"):
            ex.run({**params, **data, "bogus": data["x"]})
        with pytest.raises(ValueError, match="'w0'"):
            ex.run({"x": data["x"]})

        gt = _graph()
        tparams, tdata = _params_and_data(gt)
        tstaged = lower_train_stages(gt, plan_sbp(gt),
                                     partition_stages(gt, S), list(tparams),
                                     mesh=gt.placement.to_mesh())
        tex = TrainPipelineExecutor(tstaged, tparams, ["x", "labels"], M)
        with pytest.raises(ValueError, match="'mystery'"):
            tex.step({**tdata, "mystery": tdata["x"]})
        with pytest.raises(ValueError, match="'labels'"):
            tex.step({"x": tdata["x"]})


class TestDeprecatedShims:
    def test_make_graph_train_step_warns_and_matches_api(self):
        from repro.train.steps import make_graph_train_step

        g = _graph()
        params, data = _params_and_data(g)
        with pytest.warns(DeprecationWarning, match="api.compile"):
            mono = make_graph_train_step(g, g.placement.to_mesh(),
                                         list(params), ["x", "labels"],
                                         num_microbatches=M)
        sess = api.compile(g, mode="train", backend="monolithic",
                           params=dict(params), num_microbatches=M)
        cur = dict(params)
        for k in range(2):
            ml, mg, cur = mono.step(cur, data)
            res = sess.step(**data)
            assert bool(ml == res.loss)
            for n in params:
                assert np.array_equal(np.asarray(mg[n]),
                                      np.asarray(res.grads[n]))
                assert np.array_equal(np.asarray(cur[n]),
                                      np.asarray(res.params[n]))

    def test_make_pipeline_train_step_warns_and_returns_executor(self):
        from repro.runtime import TrainPipelineExecutor
        from repro.train.steps import make_pipeline_train_step

        g = _graph()
        params, data = _params_and_data(g)
        with pytest.warns(DeprecationWarning, match="api.compile"):
            pipe = make_pipeline_train_step(g, dict(params), ["x", "labels"],
                                            num_microbatches=M, num_stages=S,
                                            mesh=g.placement.to_mesh())
        assert isinstance(pipe, TrainPipelineExecutor)
        # historical default schedule preserved: 1F1B quotas S-s
        assert pipe.regs == [S - s for s in range(S)]
        loss, grads, new_params = pipe.step(data)
        assert np.isfinite(float(loss))


class TestSessionSurface:
    def test_history_and_metrics_accumulate(self):
        g = _graph()
        params, data = _params_and_data(g)
        sess = api.compile(g, mode="train", stages=S, params=dict(params),
                           num_microbatches=M)
        r0 = sess.step(**data)
        r1 = sess.step(**data)
        assert [h["step"] for h in sess.history] == [0, 1]
        assert r0.metrics["step"] == 0 and r1.metrics["step"] == 1
        assert r1.metrics["peak_inflight"] <= max(sess.regs)
        assert r1.metrics["makespan"] > 0
        # loss falls under SGD on this convex-ish toy
        assert float(r1.loss) < float(r0.loss)

    def test_load_params_restarts_trajectory(self):
        g = _graph()
        params, data = _params_and_data(g)
        a = api.compile(g, mode="train", stages=S, params=dict(params),
                        num_microbatches=M)
        b = api.compile(g, mode="train", stages=S, params=dict(params),
                        num_microbatches=M)
        a.step(**data)
        a.load_params(params)          # rewind to the initial weights
        ra, rb = a.step(**data), b.step(**data)
        assert bool(ra.loss == rb.loss)
        for n in params:
            assert np.array_equal(np.asarray(ra.params[n]),
                                  np.asarray(rb.params[n]))

    def test_top_level_reexports(self):
        import repro

        assert repro.compile is api.compile
        assert repro.Session is api.Session
        assert repro.assert_sessions_match is api.assert_sessions_match
