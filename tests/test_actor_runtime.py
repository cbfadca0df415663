"""Actor runtime tests: the paper's §4 protocol, Figs 2/6/8 scenarios."""
import numpy as np
import pytest

from repro.runtime import (
    ActorSpec, CommModel, ThreadedRuntime, analyze, make_actor_id,
    parse_actor_id, pipeline_specs, plan_registers, simulate)


def _noop(*a):
    return 0


class TestAddressing:
    def test_roundtrip(self):
        aid = make_actor_id(3, 7, 2, 12345)
        assert parse_actor_id(aid) == (3, 7, 2, 12345)
        assert aid < (1 << 64)

    def test_out_of_range(self):
        with pytest.raises(ValueError):
            make_actor_id(1 << 12, 0, 0, 0)

    def test_roundtrip_at_field_maxima(self):
        """Every field at its widest legal value survives the 64-bit pack
        (node/thread 12 bits, queue 8, index 32) — off-by-one masking in
        either direction would corrupt a neighbouring field here."""
        fields = ((1 << 12) - 1, (1 << 12) - 1, (1 << 8) - 1, (1 << 32) - 1)
        aid = make_actor_id(*fields)
        assert parse_actor_id(aid) == fields
        assert aid == (1 << 64) - 1
        assert parse_actor_id(make_actor_id(0, 0, 0, 0)) == (0, 0, 0, 0)

    @pytest.mark.parametrize("field,bad", [
        ("node", (1 << 12, 0, 0, 0)),
        ("thread", (0, 1 << 12, 0, 0)),
        ("queue", (0, 0, 1 << 8, 0)),
        ("actor", (0, 0, 0, 1 << 32)),
        ("node", (-1, 0, 0, 0)),
        ("actor", (0, 0, 0, -1)),
    ])
    def test_each_field_rejected_past_its_width(self, field, bad):
        """One past the width (and negatives) must fail fast *naming the
        field*, not silently alias into a neighbouring field's bits."""
        with pytest.raises(ValueError, match=field):
            make_actor_id(*bad)

    def test_ids_unique_and_hierarchical(self):
        ids = {make_actor_id(n, t, 0, i)
               for n in range(3) for t in range(3) for i in range(5)}
        assert len(ids) == 45


class TestProtocol:
    def test_chain_runs_all_batches(self):
        specs = [
            ActorSpec("src", _noop, (), out_regs=2, max_fires=10, thread=0),
            ActorSpec("mid", lambda x: x + 1, ("src",), out_regs=2, thread=1),
            ActorSpec("sink", lambda x: x, ("mid",), out_regs=2, thread=2),
        ]
        res = simulate(specs)
        assert not res.deadlocked
        assert res.fires == {"src": 10, "mid": 10, "sink": 10}

    def test_counters_bounded_by_quota(self):
        """Back-pressure: fast producer never exceeds its register quota even
        when the consumer is 10x slower (credit-based flow control, §4.3)."""
        for quota in (1, 2, 4):
            specs = [
                ActorSpec("fast", _noop, (), out_regs=quota, max_fires=50,
                          duration=0.1, thread=0),
                ActorSpec("slow", _noop, ("fast",), out_regs=1, duration=1.0,
                          thread=1),
            ]
            res = simulate(specs)
            assert not res.deadlocked
            assert res.peak_regs["fast"] <= quota
            # with quota q, producer is exactly q batches ahead at steady state
            assert res.fires["fast"] == 50 and res.fires["slow"] == 50

    def test_zero_copy_reference_passing(self):
        """Same payload object flows producer -> consumer (no copy)."""
        big = np.arange(1024)
        seen = []
        specs = [
            ActorSpec("p", lambda: big, (), out_regs=2, max_fires=3),
            ActorSpec("c", lambda x: seen.append(x), ("p",), out_regs=1),
        ]
        res = simulate(specs)
        assert not res.deadlocked
        assert all(x is big for x in seen)

    def test_multi_consumer_refcount(self):
        """A register referenced by 2 consumers recycles only after both ack;
        producer with quota 1 therefore waits for the slower consumer."""
        specs = [
            ActorSpec("p", _noop, (), out_regs=1, max_fires=5, duration=0.1),
            ActorSpec("c_fast", _noop, ("p",), out_regs=1, duration=0.1, thread=1),
            ActorSpec("c_slow", _noop, ("p",), out_regs=1, duration=2.0, thread=2),
        ]
        res = simulate(specs)
        assert not res.deadlocked
        # the slow consumer paces everyone: makespan >= 5 * 2.0
        assert res.makespan >= 10.0
        assert res.fires == {"p": 5, "c_fast": 5, "c_slow": 5}


class TestFigure2:
    """Resource-sharing scenario: two movers feed two ops on one device with
    a memory budget of 3 register units. With explicit register quotas the
    actor runtime completes; no OOM and no deadlock (paper Fig 2)."""

    def test_no_deadlock_under_contention(self):
        specs = [
            ActorSpec("M1", _noop, (), out_regs=1, max_fires=8, thread=0,
                      duration=0.2),
            ActorSpec("M2", _noop, (), out_regs=1, max_fires=8, thread=0,
                      duration=0.2),
            # O1 "needs more memory": quota 1; O2 small: quota 2 — both on
            # the same compute thread 1 (shared device)
            ActorSpec("O1", _noop, ("M1",), out_regs=1, duration=1.0, thread=1),
            ActorSpec("O2", _noop, ("M2",), out_regs=2, duration=0.5, thread=1),
        ]
        res = simulate(specs)
        assert not res.deadlocked
        assert res.fires["O1"] == 8 and res.fires["O2"] == 8
        # total register residency never exceeds the static plan
        assert res.peak_regs["M1"] <= 1 and res.peak_regs["M2"] <= 1
        assert res.peak_regs["O1"] <= 1 and res.peak_regs["O2"] <= 2


class TestFigure6:
    """Register-count pipelining (paper Fig 6): actor1 with 3 out registers,
    actor2/actor3 with 2 — all three actors act concurrently at time2."""

    def test_pipelining_overlap(self):
        specs = [
            ActorSpec("a1", _noop, (), out_regs=3, max_fires=12, duration=1.0,
                      thread=0),
            ActorSpec("a2", _noop, ("a1",), out_regs=2, duration=1.0, thread=1),
            ActorSpec("a3", _noop, ("a2",), out_regs=2, duration=1.0, thread=2),
        ]
        res = simulate(specs, comm=CommModel(same_node=0.0))
        assert not res.deadlocked
        # perfect pipeline: makespan ~ 12 + 2 (fill) not 36 (serial)
        assert res.makespan <= 15.0 + 1e-6
        # all three actors busy simultaneously at some point
        def busy_at(name, t):
            return any(s <= t < e for s, e in res.history[name])
        assert any(busy_at("a1", t) and busy_at("a2", t) and busy_at("a3", t)
                   for t in np.arange(0, res.makespan, 0.5))

    def test_single_register_serializes(self):
        """With quota 1 everywhere the same chain degrades toward serial."""
        def mk(q):
            return [
                ActorSpec("a1", _noop, (), out_regs=q, max_fires=12,
                          duration=1.0, thread=0),
                ActorSpec("a2", _noop, ("a1",), out_regs=q, duration=1.0,
                          thread=1),
                ActorSpec("a3", _noop, ("a2",), out_regs=q, duration=1.0,
                          thread=2),
            ]
        res1 = simulate(mk(1), comm=CommModel(same_node=0.0))
        res2 = simulate(mk(2), comm=CommModel(same_node=0.0))
        assert res2.makespan < res1.makespan
        assert not res1.deadlocked and not res2.deadlocked


class TestThreadedRuntime:
    def test_real_threads_compute(self):
        """Actors on real OS threads compute a correct sum via the protocol."""
        acc = []
        specs = [
            ActorSpec("src", lambda: len(acc), (), out_regs=2, max_fires=20,
                      node=0, thread=0),
            ActorSpec("sq", lambda x: x * x, ("src",), out_regs=2, node=0,
                      thread=1),
            ActorSpec("sink", lambda x: acc.append(x), ("sq",), out_regs=1,
                      node=0, thread=2),
        ]
        rt = ThreadedRuntime(specs, collect_outputs_of="sq")
        outs = rt.run(timeout=30.0)
        assert len(outs) == 20
        assert len(acc) == 20

    def test_worker_exception_propagates(self):
        def boom(x):
            raise RuntimeError("kaboom")
        specs = [
            ActorSpec("src", _noop, (), out_regs=1, max_fires=3, thread=0),
            ActorSpec("bad", boom, ("src",), out_regs=1, thread=1),
        ]
        with pytest.raises(RuntimeError, match="kaboom"):
            ThreadedRuntime(specs).run(timeout=10.0)

    def test_run_is_reusable(self):
        """A runtime is built once and re-run per epoch: actors reset at the
        start of each run (fire counts, registers, instrumentation), so two
        runs yield identical results and counters stay inspectable between
        them — the persistent executors rely on this."""
        seen = []
        specs = [
            ActorSpec("src", lambda version: version, (), out_regs=2,
                      max_fires=3, thread=0, wants_version=True),
            ActorSpec("sink", lambda x: seen.append(x) or x, ("src",),
                      out_regs=1, thread=1),
        ]
        rt = ThreadedRuntime(specs, collect_outputs_of="sink")
        assert rt.run(timeout=30.0) == [0, 1, 2]
        # post-run counters inspectable until the next run resets them
        assert rt.by_name["src"].fired == 3
        assert rt.last_fired == {"src": 3, "sink": 3}
        assert rt.run(timeout=30.0) == [0, 1, 2]
        assert seen == [0, 1, 2, 0, 1, 2]

    def test_run_fires_override_and_ctx(self):
        """Per-epoch `fires` overrides the spec bound (serve rounds vary
        their work count) and `ctx` reaches on_epoch hooks before any
        fire; unknown names are rejected."""
        base = [10]

        def set_base(v):
            if v is not None:
                base[0] = v

        specs = [
            ActorSpec("src", lambda version: base[0] + version, (),
                      out_regs=2, max_fires=0, thread=0, wants_version=True,
                      on_epoch=set_base),
            ActorSpec("sink", lambda x: x, ("src",), out_regs=1, thread=1),
        ]
        rt = ThreadedRuntime(specs, collect_outputs_of="sink")
        assert rt.run(fires={"src": 2}, timeout=30.0) == [10, 11]
        assert rt.run(ctx={"src": 100}, fires={"src": 3},
                      timeout=30.0) == [100, 101, 102]
        with pytest.raises(ValueError, match="unknown actor"):
            rt.run(ctx={"nope": 1}, fires={"src": 1})

    def test_timeout_names_unfired_actors(self):
        """A hung epoch times out with the *unfinished bounded actors and
        their fired/max counts* in the message — the only debuggable handle
        when a distributed run wedges."""
        import threading
        gate = threading.Event()
        specs = [
            ActorSpec("src", lambda: gate.wait(timeout=30.0), (), out_regs=1,
                      max_fires=3, thread=0),
            ActorSpec("sink", lambda x: x, ("src",), out_regs=1, thread=1),
        ]
        rt = ThreadedRuntime(specs)
        try:
            with pytest.raises(TimeoutError, match=r"src=\d/3"):
                rt.run(timeout=0.3)
        finally:
            gate.set()


def _chain_specs(n=4, mid=None):
    """src(node 0) -> mid(node 1) -> sink(node 2): base+v -> x+1 -> x*2,
    each payload a float32 vector of 8, so every edge carries bytes;
    ``src`` takes a per-epoch base through ``ctx``; ``mid`` replaces the
    middle actor's body."""
    base = [0.0]

    def set_base(v):
        if v is not None:
            base[0] = float(v)

    return [
        ActorSpec("src", lambda version: {"x": np.full((8,), base[0] + version,
                                                       np.float32)},
                  (), out_regs=2, max_fires=n, node=0, thread=0,
                  wants_version=True, on_epoch=set_base),
        ActorSpec("mid", mid or (lambda p: {"x": p["x"] + 1.0}), ("src",),
                  out_regs=2, node=1, thread=0),
        ActorSpec("sink", lambda p: p["x"] * 2.0, ("mid",), out_regs=2,
                  node=2, thread=0),
    ]


def _tiny_graph():
    from repro.core.graph import LogicalGraph
    from repro.core.placement import Placement

    g = LogicalGraph(Placement(("d",), (1,), device_kind="cpu"))
    h = g.input("x", (4, 8))
    for i in range(2):
        h = g.matmul(h, g.input(f"w{i}", (8, 8)), name=f"mm{i}")
    return g


def _tiny_inputs():
    rng = np.random.default_rng(0)
    return {n: rng.normal(size=s).astype(np.float32)
            for n, s in (("x", (4, 8)), ("w0", (8, 8)), ("w1", (8, 8)))}


class _Bare:
    """A bare runtime over the 3-node chain, driven like a session."""

    def __init__(self, mid=None):
        self.rt = ThreadedRuntime(_chain_specs(mid=mid),
                                  collect_outputs_of="sink")

    def runtime(self):
        return self.rt

    def drive(self):
        return self.rt.run(timeout=30.0)

    def close(self):
        self.rt.close()


class _Infer:
    def __init__(self):
        from repro import api

        self.sess = api.compile(_tiny_graph(), mode="infer", stages=2)

    def runtime(self):
        return self.sess.executor.runtime

    def drive(self):
        return self.sess.run(**_tiny_inputs())

    def close(self):
        self.sess.close()


class _Serve:
    def __init__(self):
        from repro import api
        from repro.configs.registry import get_config

        cfg = get_config("qwen3-1.7b").reduced()
        self.vocab = cfg.vocab_size
        self.sess = api.compile(cfg, mode="serve", stages=2, num_groups=1,
                                group_size=2, max_prompt_len=8,
                                max_new_tokens=2, cache_len=16)

    def runtime(self):
        return self.sess.executor.runtime

    def drive(self):
        toks = np.arange(5, dtype=np.int32) % self.vocab
        return self.sess.generate([(toks, 2)])

    def close(self):
        self.sess.close()


_OWNERS = {"runtime": _Bare, "infer-session": _Infer,
           "serve-session": _Serve}


def _assert_no_live_worker(threads):
    assert threads, "the epoch started no worker thread"
    alive = [t.name for t in threads if t.is_alive()]
    assert not alive, f"worker threads still alive: {alive}"


class TestThreadedRuntimeLifecycle:
    """The one actor runtime across epochs, at close() and after a fault:
    results and instrumentation per epoch, and no worker thread left
    behind, for a bare runtime and for the sessions built on it."""

    def test_three_node_chain_reuse_fires_and_edges(self):
        """One persistent runtime over a 3-node chain: correct results,
        epoch reuse, per-epoch ctx and fires overrides, per-edge byte
        accounting."""
        with ThreadedRuntime(_chain_specs(n=4),
                             collect_outputs_of="sink") as rt:
            outs = rt.run(timeout=30.0)
            assert [float(o[0]) for o in outs] == [
                (v + 1.0) * 2.0 for v in range(4)]
            assert all(o.shape == (8,) for o in outs)
            assert rt.last_fired == {"src": 4, "mid": 4, "sink": 4}
            # each hop carried 4 fires x 8 float32
            for edge in (("src", "mid"), ("mid", "sink")):
                assert rt.last_edge_bytes[edge] == 4 * 8 * 4
            outs = rt.run(ctx={"src": 100.0}, fires={"src": 2}, timeout=30.0)
            assert [float(o[0]) for o in outs] == [202.0, 204.0]
            assert rt.last_fired["src"] == 2
            assert rt.last_edge_bytes[("src", "mid")] == 2 * 8 * 4
            with pytest.raises(ValueError, match="unknown actor"):
                rt.run(ctx={"nope": 1}, fires={"src": 1})

    @pytest.mark.parametrize("owner", sorted(_OWNERS))
    def test_close_is_idempotent_and_leaves_no_worker(self, owner):
        o = _OWNERS[owner]()
        o.drive()
        threads = list(o.runtime()._engine._threads)
        o.close()
        o.close()    # second close: no-op, no error
        _assert_no_live_worker(threads)

    @pytest.mark.parametrize("owner", sorted(_OWNERS))
    def test_actor_error_surfaces_and_leaves_no_worker(self, owner,
                                                       monkeypatch):
        from repro.runtime import pipeline

        def boom(*a, **k):
            raise RuntimeError("kaboom in a stage body")

        if owner == "runtime":
            o = _Bare(mid=boom)
        else:
            # the stage actors look these up at fire time
            monkeypatch.setattr(pipeline, "_place_incoming", boom)
            monkeypatch.setattr(pipeline, "serve_stage_apply", boom)
            o = _OWNERS[owner]()
        with pytest.raises(RuntimeError, match="kaboom in a stage body"):
            o.drive()
        _assert_no_live_worker(list(o.runtime()._engine._threads))
        o.close()    # closing a failed runtime stays safe

    def test_kill_worker_surfaces_and_leaves_no_worker(self):
        from repro.runtime.chaos import (FaultPlan, KillWorker, WorkerError,
                                         WorkerKilled)

        rt = ThreadedRuntime(_chain_specs(n=4), collect_outputs_of="sink",
                             faults=FaultPlan([KillWorker("mid", fire=2)]))
        with pytest.raises(WorkerKilled, match="mid fire 2") as ei:
            rt.run(timeout=30.0)
        assert isinstance(ei.value, WorkerError)
        assert rt.last_fired["mid"] == 1
        _assert_no_live_worker(list(rt._engine._threads))
        rt.close()
        rt.close()


class TestPipelineSchedules:
    def test_1f1b_memory_vs_gpipe(self):
        """1F1B quota (=stages) matches GPipe throughput at a fraction of the
        activation memory (paper §6.5 / Megatron comparison)."""
        S, M = 4, 16
        gpipe = analyze(S, M, regs=[M] * S)
        onef1b = analyze(S, M, regs=[S] * S)
        assert onef1b.makespan <= gpipe.makespan * 1.05
        assert max(onef1b.peak_activation_regs.values()) <= S
        assert max(gpipe.peak_activation_regs.values()) >= M - 2

    def test_planner_picks_small_quota(self):
        plan = plan_registers(num_stages=4, num_microbatches=16)
        assert max(plan.regs) <= 8  # far below the GPipe-style 16
        assert plan.bubble_fraction < 0.35

    def test_more_registers_never_hurt(self):
        S, M = 3, 12
        spans = [analyze(S, M, regs=[r] * S).makespan for r in (1, 2, 3, 6)]
        assert all(a >= b - 1e-9 for a, b in zip(spans, spans[1:]))

    def test_zero_quota_rejected(self):
        """A zero/negative quota must fail fast naming the stage, not be
        silently clamped to 1 (which hid planner bugs)."""
        with pytest.raises(ValueError, match=r"stage 1 .* got 0"):
            pipeline_specs(3, 8, regs=[2, 0, 1])
        with pytest.raises(ValueError, match=r"stage 0 .* got -1"):
            pipeline_specs(2, 8, regs=[-1, 1])
