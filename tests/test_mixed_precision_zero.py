"""Mixed-precision ZeRO stream acceptance (paper §6.4 + Fig 14).

The contract: ``api.compile(graph, mode="train", zero=True,
precision="bf16", loss_scale=...)`` runs forward/backward in bfloat16 over
flat fp32 master shards held by the opt actors, and is **bit-identical**
across both backends — actors and monolithic — and across the zero/dense
layouts (the flat ``(dp, 1, chunk)`` shard is a pure
relayout of the dense fp32 master, and AdamW's math is elementwise).

Also covered here:

* static loss scaling (power-of-two: unscale-once is exact) and dynamic
  scaling via the ``scale`` actor — growth after ``growth_interval`` good
  steps, skip + backoff on a non-finite gradient norm, with the skipped
  step leaving params/moments/step-count untouched on every backend;
* bf16 leaves through the snapshot files: ``write_stage_snapshot`` ->
  ``load_snapshot`` must preserve ``bfloat16`` bitwise, in the plain and
  in the ZeRO flat-shard layout;
* option validation and ``describe()``/``opt_state_bytes()`` surfacing;
* snapshot/restore carrying the loss-scale trajectory.
"""
import tempfile

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro import api
from repro.core.graph import LogicalGraph
from repro.core.lowering import OptimizerSpec, PrecisionPolicy
from repro.core.placement import Placement
from repro.optim.adamw import AdamWState
from repro.runtime.snapshot import (load_snapshot, write_manifest,
                                    write_stage_snapshot)

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
    return OptimizerSpec.adamw(lr=_lr_schedule, grad_clip=1.0)


def _mp_kwargs(params, **extra):
    kw = dict(mode="train", params=dict(params), optimizer=_opt(),
              num_microbatches=M, zero=True, precision="bf16",
              loss_scale=1024.0)
    kw.update(extra)
    return kw


class TestFourWayBitIdentity:
    """zero=True precision='bf16' loss_scale=1024: losses, fp32 masters and
    AdamW moments bitwise across all backend/layout combinations over
    STEPS scheduled-lr steps."""

    def test_actors_threads_vs_monolithic(self):
        params, data = _params_and_data()
        mono = api.compile(_graph(), backend="monolithic",
                           **_mp_kwargs(params))
        with api.compile(_graph(), backend="actors", stages=S,
                         **_mp_kwargs(params)) as thr:
            api.assert_sessions_match(thr, mono, data, steps=STEPS)

    def test_zero_layout_matches_dense_masters(self):
        """The flat shard layout is pure bookkeeping: zero=True must equal
        zero=False at the same compute precision, bit for bit."""
        params, data = _params_and_data()
        z = api.compile(_graph(), backend="monolithic", **_mp_kwargs(params))
        d = api.compile(_graph(), backend="monolithic",
                        **_mp_kwargs(params, zero=False))
        api.assert_sessions_match(z, d, data, steps=STEPS)

    def test_masters_stay_fp32_params_surface_fp32(self):
        params, data = _params_and_data()
        with api.compile(_graph(), backend="actors", stages=S,
                         **_mp_kwargs(params)) as sess:
            res = sess.step(**data)
            for n, v in res.params.items():
                assert np.asarray(v).dtype == np.float32, n
            st = sess.opt_state
            for n in st.mu:
                assert np.asarray(st.mu[n]).dtype == np.float32
                assert np.asarray(st.nu[n]).dtype == np.float32

    def test_bf16_actually_degrades_vs_fp32(self):
        """Anti-placebo: the bf16 path must differ from full fp32 compute —
        otherwise the cast at the stage boundary is not happening."""
        params, data = _params_and_data()
        bf = api.compile(_graph(), backend="monolithic", **_mp_kwargs(params))
        fp = api.compile(_graph(), mode="train", backend="monolithic",
                         params=dict(params), optimizer=_opt(),
                         num_microbatches=M)
        lb = float(bf.step(**data).loss)
        lf = float(fp.step(**data).loss)
        assert lb != lf


class TestLossScaling:
    def test_static_scale_is_exact_for_powers_of_two(self):
        """Scaled-then-unscaled grads are bitwise equal to unscaled bf16
        training: scaling must cost nothing when nothing overflows."""
        params, data = _params_and_data()
        a = api.compile(_graph(), backend="monolithic", **_mp_kwargs(params))
        b = api.compile(_graph(), backend="monolithic",
                        **_mp_kwargs(params, loss_scale=None))
        api.assert_sessions_match(a, b, data, steps=STEPS)

    def test_metrics_carry_scale_and_skip(self):
        params, data = _params_and_data()
        sess = api.compile(_graph(), backend="monolithic",
                           **_mp_kwargs(params))
        m = sess.step(**data).metrics
        assert m["loss_scale"] == 1024.0
        assert m["skipped"] is False

    def _dynamic_policy(self, growth_interval=2):
        return PrecisionPolicy(compute_dtype="bfloat16", loss_scale="dynamic",
                               init_scale=2.0 ** 4,
                               growth_interval=growth_interval)

    def test_dynamic_growth_after_interval(self):
        params, data = _params_and_data()
        kw = _mp_kwargs(params, precision=self._dynamic_policy(),
                        loss_scale=None)
        mono = api.compile(_graph(), backend="monolithic", **kw)
        with api.compile(_graph(), backend="actors", stages=S, **kw) as thr:
            api.assert_sessions_match(thr, mono, data, steps=4)
            # 4 good steps at growth_interval=2 -> two doublings of 2**4
            assert mono.executor.loss_scale == 2.0 ** 6
            assert thr.executor.loss_scale == 2.0 ** 6

    @pytest.mark.parametrize("backend", ["monolithic", "actors"],
                             ids=["monolithic-None", "actors-threads"])
    def test_nonfinite_step_skips_and_backs_off(self, backend):
        """An inf batch in bf16 produces a non-finite grad norm: the step
        must be skipped — params, moments and step counter untouched — and
        the scale halved, identically on every backend."""
        params, data = _params_and_data()
        bad = dict(data)
        bad["x"] = np.full_like(data["x"], np.inf)
        kw = _mp_kwargs(params, precision=self._dynamic_policy(),
                        loss_scale=None)
        if backend == "actors":
            kw.update(stages=S)
        with api.compile(_graph(), backend=backend, **kw) as sess:
            r0 = sess.step(**data)          # good step
            p_before = {n: np.asarray(v) for n, v in sess.params.items()}
            st_before = sess.opt_state
            r1 = sess.step(**bad)           # skipped step
            assert r1.metrics["skipped"] is True
            assert r1.grads == {}
            assert sess.step_count == 1     # schedule index did not advance
            assert sess.executor.loss_scale == 2.0 ** 3   # backed off
            for n, v in sess.params.items():
                np.testing.assert_array_equal(np.asarray(v), p_before[n],
                                              err_msg=n)
            assert int(sess.opt_state.step) == int(st_before.step)
            r2 = sess.step(**data)          # recovers at the lower scale
            assert r2.metrics["skipped"] is False
            assert r2.metrics["loss_scale"] == 2.0 ** 3
            assert r0.metrics["skipped"] is False

    def test_skip_trajectories_match_across_backends(self):
        params, data = _params_and_data()
        bad = dict(data)
        bad["x"] = np.full_like(data["x"], np.inf)
        kw = _mp_kwargs(params, precision=self._dynamic_policy(),
                        loss_scale=None)
        mono = api.compile(_graph(), backend="monolithic", **kw)
        with api.compile(_graph(), backend="actors", stages=S, **kw) as thr:
            for batch in (data, bad, data, data):
                rm, rt = mono.step(**batch), thr.step(**batch)
                assert rm.metrics["skipped"] == rt.metrics["skipped"]
                assert rm.metrics["loss_scale"] == rt.metrics["loss_scale"]
                if not rm.metrics["skipped"]:
                    assert float(rm.loss) == float(rt.loss)
            for n, v in mono.params.items():
                np.testing.assert_array_equal(np.asarray(thr.params[n]),
                                              np.asarray(v), err_msg=n)


class TestBf16Snapshot:
    """bf16 leaves written by ``write_stage_snapshot`` come back from
    ``load_snapshot`` bitwise, with the ``bfloat16`` dtype intact (``np.save``
    stores an ml_dtypes array as raw 2-byte records; the manifest's dtype
    must restore it)."""

    @staticmethod
    def _bits(x):
        return np.asarray(x).view(np.uint16)

    def test_plain_layout_roundtrips_bitwise(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(7, 3)), jnp.bfloat16)
        mu = jnp.asarray(rng.normal(size=(7, 3)), jnp.bfloat16)
        nu = jnp.asarray(rng.normal(size=(7, 3)) ** 2, jnp.float32)
        with tempfile.TemporaryDirectory() as d:
            write_stage_snapshot(
                d, 1, 0, {"w": w},
                opt_state=AdamWState(jnp.asarray(1, jnp.int32),
                                     {"w": mu}, {"w": nu}))
            write_manifest(d, 1, [0])
            params, opt, step, _ = load_snapshot(d)
        assert step == 1
        assert params["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(self._bits(params["w"]), self._bits(w))
        assert opt.mu["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(self._bits(opt.mu["w"]),
                                      self._bits(mu))
        assert opt.nu["w"].dtype == np.float32
        np.testing.assert_array_equal(opt.nu["w"], np.asarray(nu))

    def test_zero_flat_shards_roundtrip_bitwise(self):
        """A (dp, 1, chunk) bf16 shard is gathered back to the logical
        shape with its dtype and bits intact."""
        dp, shape = 2, (3, 5)
        full = jnp.asarray(np.random.default_rng(1).normal(size=shape),
                           jnp.bfloat16)
        chunk = -(-full.size // dp)
        flat = jnp.pad(full.reshape(-1), (0, dp * chunk - full.size))
        with tempfile.TemporaryDirectory() as d:
            write_stage_snapshot(d, 2, 0, {"w": flat.reshape(dp, 1, chunk)},
                                 zero={"dp": dp, "shapes": {"w": shape}})
            write_manifest(d, 2, [0])
            params, opt, step, _ = load_snapshot(d)
        assert step == 2 and opt is None
        got = params["w"]
        assert got.shape == shape and got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(self._bits(got), self._bits(full))


class TestOptionValidation:
    def test_rejected_outside_train_mode(self):
        for kw in ({"zero": True}, {"precision": "bf16"},
                   {"loss_scale": 2.0}):
            with pytest.raises(ValueError, match="mode='train'"):
                api.compile(_graph(), mode="infer", **kw)

    def test_zero_requires_adamw(self):
        params, _ = _params_and_data()
        with pytest.raises(ValueError, match="adamw"):
            api.compile(_graph(), mode="train", params=dict(params),
                        zero=True)     # default SGD

    def test_zero_requires_a_data_axis(self):
        placement = Placement(("row", "col"), (1, 1), device_kind="cpu")
        g = LogicalGraph(placement)
        h = g.input("x", (B, W))
        labels = g.input("labels", (B,), dtype="int32")
        w = g.input("w0", (W, W))
        g.softmax_xent(g.matmul(h, w, name="mm0"), labels, name="loss")
        params = {"w0": np.zeros((W, W), np.float32)}
        with pytest.raises(ValueError, match="data axis"):
            api.compile(g, mode="train", params=params, optimizer=_opt(),
                        zero=True)

    def test_loss_scale_requires_bf16(self):
        params, _ = _params_and_data()
        with pytest.raises(ValueError, match="precision"):
            api.compile(_graph(), mode="train", params=dict(params),
                        optimizer=_opt(), loss_scale=2.0)
        with pytest.raises(ValueError, match="bfloat16"):
            api.compile(_graph(), mode="train", params=dict(params),
                        optimizer=_opt(), precision="fp32", loss_scale=2.0)

    def test_unknown_precision_string(self):
        params, _ = _params_and_data()
        with pytest.raises(ValueError, match="precision"):
            api.compile(_graph(), mode="train", params=dict(params),
                        optimizer=_opt(), precision="fp8")

    def test_bad_policy_values(self):
        with pytest.raises(ValueError):
            PrecisionPolicy(compute_dtype="float16")
        with pytest.raises(ValueError):
            PrecisionPolicy(loss_scale=-1.0)
        with pytest.raises(ValueError):
            PrecisionPolicy(loss_scale="sometimes")


class TestSurfacing:
    def test_describe_reports_precision_zero_and_bytes(self):
        params, data = _params_and_data()
        with api.compile(_graph(), backend="actors", stages=S,
                         **_mp_kwargs(params)) as sess:
            sess.step(**data)
            text = sess.describe()
        assert "precision: compute=bfloat16 masters=float32" in text
        assert "loss_scale=1024.0" in text
        assert "zero: dp=1" in text
        assert "optimizer-state bytes/device:" in text

    def test_opt_state_bytes_accounting(self):
        """Mixed precision holds masters+mu+nu fp32 (3 floats/element);
        plain AdamW holds mu+nu (2). N = S*W*W elements here, dp=1."""
        params, data = _params_and_data()
        n_elems = S * W * W
        with api.compile(_graph(), backend="actors", stages=S,
                         **_mp_kwargs(params)) as mp_sess:
            mp_sess.step(**data)
            mp_bytes = sum(mp_sess.executor.opt_state_bytes().values())
        with api.compile(_graph(), mode="train", backend="actors", stages=S,
                         params=dict(params), optimizer=_opt(),
                         num_microbatches=M) as dense_sess:
            dense_sess.step(**data)
            dense_bytes = sum(dense_sess.executor.opt_state_bytes().values())
        assert mp_bytes == 3 * 4 * n_elems
        assert dense_bytes == 2 * 4 * n_elems
        # both engines account identically
        mono = api.compile(_graph(), backend="monolithic",
                           **_mp_kwargs(params))
        mono.step(**data)
        assert sum(mono.executor.opt_state_bytes().values()) == mp_bytes

    def test_last_edge_bytes_surface(self):
        params, data = _params_and_data()
        with api.compile(_graph(), backend="actors", stages=S,
                         **_mp_kwargs(params)) as sess:
            sess.step(**data)
            eb = sess.last_edge_bytes
            assert eb and all(isinstance(v, int) for v in eb.values())
        mono = api.compile(_graph(), backend="monolithic",
                           **_mp_kwargs(params))
        assert mono.last_edge_bytes == {}


class TestSnapshotCarriesScale:
    def test_restore_resumes_scale_trajectory(self):
        """A snapshot taken under dynamic scaling records the scale to
        resume with; restore must continue the interrupted trajectory
        bitwise — including the scale the next step runs under."""
        params, data = _params_and_data()
        pol = PrecisionPolicy(compute_dtype="bfloat16", loss_scale="dynamic",
                              init_scale=2.0 ** 4, growth_interval=2)
        kw = _mp_kwargs(params, precision=pol, loss_scale=None)
        ref = api.compile(_graph(), backend="monolithic", **kw)
        ref_losses = [float(ref.step(**data).loss) for _ in range(4)]
        with tempfile.TemporaryDirectory() as d:
            with api.compile(_graph(), backend="actors", stages=S,
                             snapshot_dir=d, **kw) as sess:
                losses = [float(sess.step(**data).loss) for _ in range(2)]
            with api.compile(_graph(), backend="actors", stages=S,
                             restore=d, **kw) as res:
                # two good steps at growth_interval=2 -> scale grew once
                assert res.executor.loss_scale == 2.0 ** 5
                assert res.step_count == 2
                losses += [float(res.step(**data).loss) for _ in range(2)]
                final = res.params
        assert losses == ref_losses
        for n, v in ref.params.items():
            np.testing.assert_array_equal(np.asarray(final[n]),
                                          np.asarray(v), err_msg=n)
