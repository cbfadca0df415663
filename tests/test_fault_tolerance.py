"""Kill-and-resume bit-identity (the elastic-training acceptance test).

The contract under test, end to end:

* ``compile(snapshot_dir=...)`` makes every training step emit an async
  per-stage snapshot (``snap{s}`` actors off the hot path), finalized by a
  driver-side MANIFEST — so ``latest_snapshot(dir)`` always equals the
  number of *completed* steps, even when a fault kills the run mid-step.
* ``compile(faults=FaultPlan([KillWorker(actor, fire=k)]))`` kills the
  named actor's worker at its k-th cumulative fire; the driver sees a
  ``WorkerError``.
* ``compile(restore=dir)`` resumes from the newest completed snapshot —
  params, Adam moments, and the step counter the lr schedule indexes.

Acceptance: for every (actor, fire-index) of a 3-step AdamW run, kill the
run there, resume from the last completed snapshot, and the combined loss
history AND final params/optimizer state are bitwise identical to an
uninterrupted run (the monolithic reference — itself pinned bit-identical
to the actor pipeline in test_api.py).
"""
import tempfile

import numpy as np
import pytest

from repro import api
from repro.core.graph import LogicalGraph
from repro.core.lowering import OptimizerSpec
from repro.core.placement import Placement
from repro.runtime.chaos import (FaultPlan, KillWorker, WorkerError,
                                 WorkerKilled)
from repro.runtime.snapshot import (latest_snapshot, list_snapshots,
                                    load_snapshot)

B, W, S, M, STEPS = 8, 8, 2, 2, 3


def _graph():
    placement = Placement(("d",), (1,), device_kind="cpu")
    g = LogicalGraph(placement)
    h = g.input("x", (B, W))
    labels = g.input("labels", (B,), dtype="int32")
    for i in range(S):
        w = g.input(f"w{i}", (W, W))
        h = g.matmul(h, w, name=f"mm{i}")
        if i < S - 1:
            h = g.unary(h, "relu", name=f"relu{i}")
    g.softmax_xent(h, labels, name="loss")
    return g


def _params_and_data(seed=0):
    rng = np.random.default_rng(seed)
    params = {f"w{i}": (rng.normal(size=(W, W)) * 0.1).astype(np.float32)
              for i in range(S)}
    data = {"x": rng.normal(size=(B, W)).astype(np.float32),
            "labels": rng.integers(0, W, size=(B,)).astype(np.int32)}
    return params, data


def _lr_schedule(s):
    return 1e-3 * 0.9 ** s


def _opt():
    # schedule + clipping: restore must also bring back the step counter
    # (lr schedule index) and the Adam moments for bits to match
    return OptimizerSpec.adamw(lr=_lr_schedule, grad_clip=1.0)


@pytest.fixture(scope="module")
def ref():
    """Uninterrupted STEPS-step reference: losses, final params, opt state."""
    params, data = _params_and_data()
    sess = api.compile(_graph(), mode="train", backend="monolithic",
                       params=dict(params), optimizer=_opt(),
                       num_microbatches=M)
    losses = [float(sess.step(**data).loss) for _ in range(STEPS)]
    return {"params0": params, "data": data, "losses": losses,
            "final_params": sess.params, "opt_state": sess.opt_state}


def _assert_matches_ref(ref, losses, params, opt_state):
    assert losses == ref["losses"]
    for n, v in ref["final_params"].items():
        assert np.array_equal(np.asarray(params[n]), np.asarray(v)), n
    rs = ref["opt_state"]
    assert int(opt_state.step) == int(rs.step)
    for n in rs.mu:
        assert np.array_equal(np.asarray(opt_state.mu[n]),
                              np.asarray(rs.mu[n])), n
        assert np.array_equal(np.asarray(opt_state.nu[n]),
                              np.asarray(rs.nu[n])), n


def _kill_and_resume(ref, actor, fire):
    params, data = ref["params0"], ref["data"]
    with tempfile.TemporaryDirectory() as d:
        kw = dict(mode="train", backend="actors", stages=S,
                  params=dict(params), optimizer=_opt(), num_microbatches=M)
        sess = api.compile(_graph(), snapshot_dir=d,
                           faults=FaultPlan([KillWorker(actor, fire=fire)]),
                           **kw)
        losses, killed = [], False
        try:
            for _ in range(STEPS):
                losses.append(float(sess.step(**data).loss))
        except WorkerError:
            killed = True
        finally:
            sess.close()
        assert killed, f"kill at {actor} fire {fire} never triggered"
        # the core snapshot invariant: completed snapshots == completed steps
        n = latest_snapshot(d) or 0
        assert n == len(losses) < STEPS
        if n:
            res = api.compile(_graph(), restore=d, **kw)
            assert res.step_count == n
        else:
            res = api.compile(_graph(), **kw)    # died before any snapshot
        try:
            losses += [float(res.step(**data).loss)
                       for _ in range(STEPS - n)]
            final_params, opt_state = res.params, res.opt_state
        finally:
            res.close()
        _assert_matches_ref(ref, losses, final_params, opt_state)


# every fire index of the stage actors over a 3-step run: f{s} and b{s}
# each fire M*STEPS times, opt{s}/snap{s} once per step
_THREAD_CASES = (
    [(f"f{s}", k) for s in range(S) for k in range(1, M * STEPS + 1)]
    + [(f"b{s}", k) for s in range(S) for k in range(1, M * STEPS + 1)]
    + [(f"opt{s}", k) for s in range(S) for k in range(1, STEPS + 1)]
    + [("snap0", 2)]
)


class TestKillAndResumeThreads:
    @pytest.mark.parametrize("actor,fire", _THREAD_CASES,
                             ids=[f"{a}-fire{k}" for a, k in _THREAD_CASES])
    def test_bit_identical(self, ref, actor, fire):
        _kill_and_resume(ref, actor, fire)

    def test_worker_killed_is_a_worker_error(self):
        assert issubclass(WorkerKilled, WorkerError)


class TestSnapshotRestoreSurface:
    def test_snapshot_every(self, ref):
        params, data = ref["params0"], ref["data"]
        with tempfile.TemporaryDirectory() as d:
            with api.compile(_graph(), mode="train", stages=S,
                             params=dict(params), optimizer=_opt(),
                             num_microbatches=M, snapshot_dir=d,
                             snapshot_every=2) as sess:
                for _ in range(STEPS):
                    sess.step(**data)
            assert list_snapshots(d) == [2]

    def test_restore_onto_monolithic_backend(self, ref):
        """Partition-agnostic restore: a snapshot from a 2-stage actor run
        resumes the whole-graph monolithic reference bit-identically."""
        params, data = ref["params0"], ref["data"]
        with tempfile.TemporaryDirectory() as d:
            with api.compile(_graph(), mode="train", stages=S,
                             params=dict(params), optimizer=_opt(),
                             num_microbatches=M, snapshot_dir=d) as sess:
                losses = [float(sess.step(**data).loss)]
            mono = api.compile(_graph(), mode="train", backend="monolithic",
                               params=dict(params), optimizer=_opt(),
                               num_microbatches=M, restore=d)
            assert mono.step_count == 1
            losses += [float(mono.step(**data).loss)
                       for _ in range(STEPS - 1)]
            _assert_matches_ref(ref, losses, mono.params, mono.opt_state)

    def test_load_snapshot_roundtrip(self, ref):
        params, data = ref["params0"], ref["data"]
        with tempfile.TemporaryDirectory() as d:
            with api.compile(_graph(), mode="train", stages=S,
                             params=dict(params), optimizer=_opt(),
                             num_microbatches=M, snapshot_dir=d) as sess:
                for _ in range(STEPS):
                    sess.step(**data)
                want_params, want_opt = sess.params, sess.opt_state
            got_params, got_opt, step, meta = load_snapshot(d)
            assert step == STEPS
            assert meta["num_stages"] == S and meta["stateful"]
            for n, v in want_params.items():
                assert np.array_equal(np.asarray(got_params[n]),
                                      np.asarray(v)), n
            assert int(got_opt.step) == int(want_opt.step)

    def test_restore_empty_dir_raises(self, ref):
        params, _ = ref["params0"], ref["data"]
        with tempfile.TemporaryDirectory() as d:
            with pytest.raises(FileNotFoundError, match="no completed"):
                api.compile(_graph(), mode="train", stages=S,
                            params=dict(params), optimizer=_opt(),
                            num_microbatches=M, restore=d)

    def test_train_only_options_rejected(self):
        g = _graph()
        with pytest.raises(ValueError, match="mode='train'"):
            api.compile(g, mode="infer", snapshot_dir="/tmp/x")
        with pytest.raises(ValueError, match="mode='train'"):
            api.compile(g, mode="infer", faults=FaultPlan([]))
        with pytest.raises(ValueError, match="mode='train'"):
            api.compile(g, mode="infer", snapshot_every=2)

    def test_actors_only_options_rejected(self, ref):
        params = ref["params0"]
        for kw in ({"snapshot_dir": "/tmp/x"}, {"faults": FaultPlan([])}):
            with pytest.raises(ValueError, match="backend='actors'"):
                api.compile(_graph(), mode="train", backend="monolithic",
                            params=dict(params), **kw)

    def test_snapshot_every_without_dir_rejected(self, ref):
        with pytest.raises(ValueError, match="snapshot_every"):
            api.compile(_graph(), mode="train", params=dict(ref["params0"]),
                        snapshot_every=2)


# ---------------------------------------------------------------------------
# Mixed-precision ZeRO rows: the sharded snapshot is partition-agnostic too.
# ---------------------------------------------------------------------------

S4 = 4


def _zero_graph():
    """A 4-layer variant so the snapshot under test is written by a 4-stage
    pipeline and restored onto a different cut."""
    placement = Placement(("d",), (1,), device_kind="cpu")
    g = LogicalGraph(placement)
    h = g.input("x", (B, W))
    labels = g.input("labels", (B,), dtype="int32")
    for i in range(S4):
        w = g.input(f"w{i}", (W, W))
        h = g.matmul(h, w, name=f"mm{i}")
        if i < S4 - 1:
            h = g.unary(h, "relu", name=f"relu{i}")
    g.softmax_xent(h, labels, name="loss")
    return g


def _zero_params_and_data(seed=3):
    rng = np.random.default_rng(seed)
    params = {f"w{i}": (rng.normal(size=(W, W)) * 0.1).astype(np.float32)
              for i in range(S4)}
    data = {"x": rng.normal(size=(B, W)).astype(np.float32),
            "labels": rng.integers(0, W, size=(B,)).astype(np.int32)}
    return params, data


def _zero_kw(params, **extra):
    kw = dict(mode="train", params=dict(params), optimizer=_opt(),
              num_microbatches=M, zero=True, precision="bf16",
              loss_scale=1024.0)
    kw.update(extra)
    return kw


# one kill per actor of the 4-stage ZeRO pipeline: every f{s} at fire 3
# (step 2, first microbatch), every b{s} at fire 4 (step 2, second
# microbatch), every opt{s} and snap{s} at fire 2 (step 2); b3 also at
# fire 3
_ZERO_CASES = (
    [("opt2", 2), ("b3", 3)]
    + [(f"f{s}", 3) for s in range(S4)]
    + [(f"b{s}", 4) for s in range(S4)]
    + [(f"opt{s}", 2) for s in range(S4) if s != 2]
    + [(f"snap{s}", 2) for s in range(S4)]
)


class TestZeroKillAndResume:
    """zero=True precision='bf16': kill mid-step, resume from the *sharded*
    snapshot — onto the same cut, onto a different cut, and onto the
    monolithic backend — bitwise against an uninterrupted reference."""

    @pytest.fixture(scope="class")
    def zref(self):
        params, data = _zero_params_and_data()
        sess = api.compile(_zero_graph(), backend="monolithic",
                           **_zero_kw(params))
        losses = [float(sess.step(**data).loss) for _ in range(STEPS)]
        return {"params0": params, "data": data, "losses": losses,
                "final_params": sess.params, "opt_state": sess.opt_state}

    def _run_killed(self, zref, d, actor, fire):
        params, data = zref["params0"], zref["data"]
        sess = api.compile(_zero_graph(), snapshot_dir=d,
                           faults=FaultPlan([KillWorker(actor, fire=fire)]),
                           backend="actors", stages=S4, **_zero_kw(params))
        losses, killed = [], False
        try:
            for _ in range(STEPS):
                losses.append(float(sess.step(**data).loss))
        except WorkerError:
            killed = True
        finally:
            sess.close()
        assert killed, f"kill at {actor} fire {fire} never triggered"
        n = latest_snapshot(d) or 0
        assert n == len(losses) < STEPS
        return losses, n

    @pytest.mark.parametrize("actor,fire", _ZERO_CASES,
                             ids=[f"{a}-fire{k}" for a, k in _ZERO_CASES])
    def test_resume_same_partition(self, zref, actor, fire):
        params, data = zref["params0"], zref["data"]
        with tempfile.TemporaryDirectory() as d:
            losses, n = self._run_killed(zref, d, actor, fire)
            with api.compile(_zero_graph(), restore=d, backend="actors",
                             stages=S4, **_zero_kw(params)) as res:
                assert res.step_count == n
                losses += [float(res.step(**data).loss)
                           for _ in range(STEPS - n)]
                final_params, opt_state = res.params, res.opt_state
        _assert_matches_ref(zref, losses, final_params, opt_state)

    def test_resume_onto_two_stages(self, zref):
        """4-stage sharded snapshot -> 2-stage pipeline: the flat shards are
        host-gathered to full tensors on load and re-sharded by the new
        cut, so the continued run is bitwise identical."""
        params, data = zref["params0"], zref["data"]
        with tempfile.TemporaryDirectory() as d:
            losses, n = self._run_killed(zref, d, "opt1", 2)
            with api.compile(_zero_graph(), restore=d, backend="actors",
                             stages=2, **_zero_kw(params)) as res:
                assert res.step_count == n
                losses += [float(res.step(**data).loss)
                           for _ in range(STEPS - n)]
                final_params, opt_state = res.params, res.opt_state
        _assert_matches_ref(zref, losses, final_params, opt_state)

    def test_resume_onto_monolithic(self, zref):
        params, data = zref["params0"], zref["data"]
        with tempfile.TemporaryDirectory() as d:
            losses, n = self._run_killed(zref, d, "f2", 4)
            res = api.compile(_zero_graph(), restore=d, backend="monolithic",
                              **_zero_kw(params))
            assert res.step_count == n
            losses += [float(res.step(**data).loss)
                       for _ in range(STEPS - n)]
        _assert_matches_ref(zref, losses, res.params, res.opt_state)

    def test_sharded_snapshot_loads_full_tensors(self, zref):
        """load_snapshot never surfaces shards: params and moments come
        back at the logical shapes regardless of the zero layout."""
        params, data = zref["params0"], zref["data"]
        with tempfile.TemporaryDirectory() as d:
            with api.compile(_zero_graph(), backend="actors", stages=S4,
                             snapshot_dir=d, **_zero_kw(params)) as sess:
                sess.step(**data)
            got_params, got_opt, step, meta = load_snapshot(d)
            assert step == 1 and meta["zero"] is True
            for n, v in params.items():
                assert got_params[n].shape == v.shape
                assert got_params[n].dtype == np.float32
                assert got_opt.mu[n].shape == v.shape
