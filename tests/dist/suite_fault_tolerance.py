"""Multi-device suite: elastic kill-and-resume across stage partitions.

The elastic-training claim of the snapshot format: a snapshot is the flat
logical state (params + merged Adam moments + step counter), not a record
of the partition that wrote it. So a 4-stage run on 8 devices (2 per stage,
the paper's MPMD placement) that is killed by fault injection mid-training
must resume — from its own per-stage snapshot files — onto a *2-stage*
partition over different device groups, and finish the trajectory the
uninterrupted reference follows.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "src"))

import numpy as np

import jax

from repro import api
from repro.core.graph import LogicalGraph
from repro.core.lowering import OptimizerSpec
from repro.core.placement import Placement
from repro.runtime import (FaultPlan, KillWorker, WorkerError,
                           latest_snapshot)

STAGES, MICROBATCHES, BATCH, WIDTH, STEPS = 4, 4, 16, 32, 3


def _graph(placement):
    g = LogicalGraph(placement)
    h = g.input("x", (BATCH, WIDTH), sbp="S(0)")
    labels = g.input("labels", (BATCH,), dtype="int32", sbp="S(0)")
    for i in range(STAGES):
        w = g.input(f"w{i}", (WIDTH, WIDTH))
        h = g.matmul(h, w, name=f"mm{i}")
        if i < STAGES - 1:
            h = g.unary(h, "relu", name=f"relu{i}")
    g.softmax_xent(h, labels, name="loss")
    return g


def _opt():
    return OptimizerSpec.adamw(lr=lambda s: 1e-3 * (0.5 ** s),
                               grad_clip=0.5)


def elastic_kill_and_resume():
    placement = Placement(("data",), (2,), device_kind="cpu")
    devs = jax.devices()
    assert len(devs) >= 8
    rng = np.random.default_rng(5)
    params = {f"w{i}": (rng.normal(size=(WIDTH, WIDTH)) * 0.5
                        ).astype(np.float32) for i in range(STAGES)}
    data = {"x": rng.normal(size=(BATCH, WIDTH)).astype(np.float32),
            "labels": rng.integers(0, WIDTH, (BATCH,)).astype(np.int32)}

    ref = api.compile(_graph(placement), mode="train", backend="monolithic",
                      params=dict(params), optimizer=_opt(),
                      num_microbatches=MICROBATCHES,
                      mesh=placement.to_mesh(devices=devs[:2]))
    ref_losses = [float(ref.step(**data).loss) for _ in range(STEPS)]

    with tempfile.TemporaryDirectory() as d:
        # 4 stages x 2 disjoint devices each, async snapshots every step,
        # f2's worker killed during step 2 (fire MICROBATCHES + 1)
        meshes4 = [placement.to_mesh(devices=devs[2 * s:2 * s + 2])
                   for s in range(STAGES)]
        sess = api.compile(
            _graph(placement), mode="train", stages=STAGES,
            params=dict(params), optimizer=_opt(),
            num_microbatches=MICROBATCHES, stage_meshes=meshes4,
            snapshot_dir=d,
            faults=FaultPlan([KillWorker("f2", fire=MICROBATCHES + 1)]))
        losses = []
        try:
            for _ in range(STEPS):
                losses.append(float(sess.step(**data).loss))
            raise AssertionError("kill never triggered")
        except WorkerError:
            pass
        finally:
            sess.close()
        n = latest_snapshot(d)
        assert n == len(losses) == 1, (n, losses)

        # resume the SAME trajectory on a different partition: 2 stages
        # over different 4-device groups
        meshes2 = [placement.to_mesh(devices=devs[0:4:2]),
                   placement.to_mesh(devices=devs[4:8:2])]
        res = api.compile(
            _graph(placement), mode="train", stages=2,
            params=dict(params), optimizer=_opt(),
            num_microbatches=MICROBATCHES, stage_meshes=meshes2,
            restore=d)
        assert res.step_count == n
        assert int(res.opt_state.step) == n
        losses += [float(res.step(**data).loss) for _ in range(STEPS - n)]
        final_params, opt_state = res.params, res.opt_state
        res.close()

    for got, want in zip(losses, ref_losses):
        assert np.allclose(got, want, rtol=1e-5), (losses, ref_losses)
    rs = ref.opt_state
    assert int(opt_state.step) == int(rs.step) == STEPS
    for nme in params:
        assert np.allclose(np.asarray(final_params[nme]),
                           np.asarray(ref.params[nme]),
                           rtol=1e-4, atol=1e-6), nme
        assert np.allclose(np.asarray(opt_state.mu[nme]),
                           np.asarray(rs.mu[nme]),
                           rtol=1e-4, atol=1e-7), nme


def zero_mixed_precision_kill_and_resume():
    """zero=True precision='bf16' at a real DP=2: the opt actors hold flat
    ``(2, 1, chunk)`` fp32 master/moment shards; a 4-stage run killed
    mid-step resumes from the sharded snapshot onto a 2-stage cut and
    finishes the uninterrupted monolithic trajectory."""
    placement = Placement(("data",), (2,), device_kind="cpu")
    devs = jax.devices()
    assert len(devs) >= 8
    rng = np.random.default_rng(9)
    params = {f"w{i}": (rng.normal(size=(WIDTH, WIDTH)) * 0.5
                        ).astype(np.float32) for i in range(STAGES)}
    data = {"x": rng.normal(size=(BATCH, WIDTH)).astype(np.float32),
            "labels": rng.integers(0, WIDTH, (BATCH,)).astype(np.int32)}
    kw = dict(mode="train", params=dict(params), optimizer=_opt(),
              num_microbatches=MICROBATCHES, zero=True, precision="bf16",
              loss_scale=2.0 ** 10)

    ref = api.compile(_graph(placement), backend="monolithic",
                      mesh=placement.to_mesh(devices=devs[:2]), **kw)
    ref_losses = [float(ref.step(**data).loss) for _ in range(STEPS)]
    assert ref.optimizer.zero_dp == 2     # the data axis folded into ZeRO

    with tempfile.TemporaryDirectory() as d:
        meshes4 = [placement.to_mesh(devices=devs[2 * s:2 * s + 2])
                   for s in range(STAGES)]
        sess = api.compile(
            _graph(placement), stages=STAGES, stage_meshes=meshes4,
            snapshot_dir=d,
            faults=FaultPlan([KillWorker("opt2", fire=2)]), **kw)
        losses = []
        try:
            for _ in range(STEPS):
                losses.append(float(sess.step(**data).loss))
            raise AssertionError("kill never triggered")
        except WorkerError:
            pass
        finally:
            sess.close()
        n = latest_snapshot(d)
        assert n == len(losses) == 1, (n, losses)

        meshes2 = [placement.to_mesh(devices=devs[0:4:2]),
                   placement.to_mesh(devices=devs[4:8:2])]
        res = api.compile(_graph(placement), stages=2,
                          stage_meshes=meshes2, restore=d, **kw)
        assert res.step_count == n
        assert int(res.opt_state.step) == n
        losses += [float(res.step(**data).loss) for _ in range(STEPS - n)]
        final_params, opt_state = res.params, res.opt_state
        res.close()

    for got, want in zip(losses, ref_losses):
        assert np.allclose(got, want, rtol=1e-5), (losses, ref_losses)
    rs = ref.opt_state
    assert int(opt_state.step) == int(rs.step) == STEPS
    for nme in params:
        # masters and moments surface fp32 at logical shapes
        assert np.asarray(final_params[nme]).dtype == np.float32
        assert np.allclose(np.asarray(final_params[nme]),
                           np.asarray(ref.params[nme]),
                           rtol=1e-4, atol=1e-6), nme
        assert np.allclose(np.asarray(opt_state.mu[nme]),
                           np.asarray(rs.mu[nme]),
                           rtol=1e-4, atol=1e-7), nme


if __name__ == "__main__":
    elastic_kill_and_resume()
    zero_mixed_precision_kill_and_resume()
    print("ALL-OK")
