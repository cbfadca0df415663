"""The program-span reduction: idle time charged to the layer whose span was
open, the actor hop, the clock check, idle gaps named by program spans;
and the stage programs' names that the decode roofline matches."""
import pathlib
import re
import types

import pytest

import benchchip_tiny  # noqa: F401  (puts the harness on sys.path)
from benchchip import registry, spans, xplane
from benchchip.spans import Span

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "tiny.xplane.pb"
T0, T1 = ("/host:CPU", 0), ("/host:CPU", 1)


def fire(actor, epoch, k, a, b, thread):
    return Span("actor.fire", a, b, thread,
                {"actor": actor, "epoch": epoch, "fire": k})


def test_gap_split_across_two_spans_on_two_threads():
    """Idle 1-9. Thread 0 holds ``runtime.round`` over 0-10; thread 1 a
    fire over 3-5 and, inside it, a sync over 4-6 that outlives it (the
    latest-started open span is the innermost one); ``serve.absorb`` 7-8
    starts after the round's, so it is innermost there."""
    sp = [Span("runtime.round", 0.0, 10.0, T0, {}),
          fire("stage0", 1, 0, 3.0, 5.0, T1),
          Span("stage.sync", 4.0, 6.0, T1, {"kind": "decode"}),
          Span("serve.absorb", 7.0, 8.0, T0, {})]
    got = spans.attribute([(1.0, 9.0)], sp)
    assert got == {"runtime": 2.0 + 1.0 + 1.0, "stage": 3.0,
                   "serve_loop": 1.0, None: 0.0}
    # no span open: outside the program
    assert spans.attribute([(10.0, 12.0)], sp)[None] == 2.0


def test_equal_starts_charge_the_shorter_span():
    sp = [Span("runtime.round", 0.0, 4.0, T0, {}),
          Span("stage.program", 0.0, 1.0, T0, {"kind": "decode"})]
    assert spans.attribute([(0.0, 2.0)], sp) == {
        "stage": 1.0, "runtime": 1.0, "serve_loop": 0.0, None: 0.0}


def synthetic():
    """One device busy 1-3 and 6-7 in a window 0-10 (``bench.generate``),
    program spans over part of it."""
    dev = {"modules": [("jit_local_decode_s0(1)", 1.0, 3.0),
                       ("jit_local_decode_s1(2)", 6.0, 7.0)],
           "ops": [("fusion.1", 1.0, 3.0), ("fusion.2", 6.0, 7.0)]}
    bench = [("bench.generate", 0.0, 10.0)]
    sp = [Span("serve.generate", 0.5, 9.0, T0, {"requests": 2}),
          Span("runtime.round", 0.6, 8.0, T0, {"round": 0, "items": 1}),
          fire("stage0", 1, 0, 0.8, 3.2, T1),
          Span("stage.sync", 1.1, 3.1, T1, {"kind": "decode"}),
          fire("stage1", 1, 0, 3.5, 7.4, T0),
          Span("stage.sync", 6.2, 7.3, T0, {"kind": "decode"}),
          Span("serve.absorb", 8.5, 8.8, T0, {"round": 0})]
    return [dev], bench, sp


def test_shares_plus_remainder_equal_the_idle_share():
    devices, bench, sp = synthetic()
    red = spans.reduce(devices, bench, sp)
    base = xplane.reduce(devices, bench)
    assert red["window_s"] == base["window_s"] == 10.0
    assert red["busy_s"] == base["busy_s"] == 3.0
    idle = red["idle_s"]
    # 0-0.5 and 9-10 outside; 0.5-0.6 and 8-9 to the serve loop (1.1);
    # 0.6-0.8 and 3.2-3.5 and 7.4-8 to the runtime (1.1); 0.8-1 and 3-3.2
    # and 3.5-6 and 7-7.4 to the stage fires (3.3)
    assert idle["serve_loop"] == pytest.approx(0.1 + 1.0)
    assert idle["runtime"] == pytest.approx(0.2 + 0.3 + 0.6)
    assert idle["stage"] == pytest.approx(0.2 + 0.2 + 2.5 + 0.4)
    assert red["idle_outside_program_s"] == pytest.approx(1.5)
    run = types.SimpleNamespace(spans=red, trace=base)
    shares = [registry.metric_reader(f"idle_{k}.batch").read(run)
              for k in ("serve_loop", "runtime", "stage")]
    total = sum(shares) + 100 * red["idle_outside_program_s"] / 10.0
    share = registry.metric_reader("device_idle_share.batch").read(run)
    assert total == pytest.approx(share) == pytest.approx(70.0)


def test_hop_excludes_the_consumers_queue_wait():
    """stage1's fire 1 waits for its own fire 0 (busy consumer) and fire 0
    for stage0's fire 0 (free consumer): both hops count from the later
    of the two ends."""
    sp = [fire("stage0", 3, 0, 0.0, 1.0, T0),
          fire("stage0", 3, 1, 1.1, 2.0, T0),
          fire("stage1", 3, 0, 1.25, 4.0, T1),     # free: 1.25 - 1.0
          fire("stage1", 3, 1, 4.5, 5.0, T1),      # busy: 4.5 - 4.0
          fire("admit", 3, 0, 0.0, 0.1, T0),
          fire("stage1", 4, 0, 9.0, 9.5, T1)]      # no producer: left out
    hops = spans.actor_hops(sp)
    assert sorted(hops) == pytest.approx([0.25, 0.5])
    devices, bench, _ = synthetic()
    red = spans.reduce(devices, [("bench.generate", 0.0, 10.0)], sp)
    assert red["actor_hop_ms"] == pytest.approx(375.0)
    run = types.SimpleNamespace(spans=red)
    assert registry.metric_reader("actor_hop_ms.batch").read(
        run) == pytest.approx(375.0)


def test_sync_lag_reads_the_program_of_the_fires_stage():
    """Each sync ends after the device end of its own stage's decode; the
    stage comes from the fire around it."""
    devices, bench, sp = synthetic()
    lags = spans.sync_lags(devices, sp)
    assert sorted(lags) == pytest.approx([0.1, 0.3])
    red = spans.reduce(devices, bench, sp)
    clock = red["clock"]
    assert clock["sync_lag_ms"] == pytest.approx(
        {"median": 200.0, "min": 100.0, "max": 300.0, "count": 2})
    # the same idle seconds, moved 0.2 s later, reported beside the shares
    moved = clock["idle_s_moved_by_median_lag"]
    assert sum(moved.values()) == pytest.approx(7.0)
    assert moved != {k or "outside": v for k, v in spans.attribute(
        [(0.0, 1.0), (3.0, 6.0), (7.0, 10.0)], sp).items()}
    assert red["idle_s"]["stage"] == pytest.approx(3.3)


def test_a_trace_without_program_spans_reads_nothing():
    """A program that opens no span: the reduction is None and the readers
    return None, not an error."""
    devices, bench, _ = synthetic()
    assert spans.reduce(devices, bench, []) is None
    run = types.SimpleNamespace(spans=None, trace=None)
    for name in ("idle_serve_loop", "idle_runtime", "idle_stage",
                 "actor_hop_ms"):
        assert registry.metric_reader(name + ".batch").read(run) is None
    # the one walk of the file reads what xplane.read_events reads
    devices, bench, program = spans.read_events(str(FIXTURE))
    assert program == []
    assert (devices, bench) == xplane.read_events(str(FIXTURE))


def test_recorded_trace_gaps_keep_their_durations_and_order():
    """On the recorded TPU trace, program spans change only the gaps'
    names: the window, the busy time and every gap's duration and place
    stay what :func:`xplane.reduce` gives."""
    devices, bench = xplane.read_events(str(FIXTURE))
    base = xplane.reduce(devices, bench)
    lo, hi = bench[0][1], bench[-1][2]
    mid = (lo + hi) / 2
    sp = [Span("serve.generate", lo, mid, T0, {}),
          Span("runtime.round", lo + 1e-6, mid, T1, {})]
    red = spans.reduce(devices, bench, sp)
    assert red["window_s"] == base["window_s"]
    assert red["busy_s"] == pytest.approx(base["busy_s"])
    assert [d for _, d in red["idle_gaps"]] == [
        d for _, d in base["idle_gaps"]]
    for (name, d), (old, _) in zip(red["idle_gaps"], base["idle_gaps"]):
        assert name in ("runtime.round", "serve.generate", old)
    assert {n for n, _ in red["idle_gaps"]} & {"runtime.round"}
    idle = base["window_s"] - base["busy_s"]
    assert sum(red["idle_s"].values()) + red[
        "idle_outside_program_s"] == pytest.approx(idle)


def test_stage_programs_carry_their_stage_and_match_the_decode_roofline():
    """The staged serve programs are named after their stage; the decode
    roofline's patterns still find every decode program, the paged gather
    and the decode scatter, and no prefill program."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh
    from repro.runtime.pipeline import make_stage_cache

    cfg = dataclasses.replace(get_config("qwen2.5-3b").reduced(),
                              vocab_size=1000)
    mesh = make_mesh((1, 1), ("data", "model"))
    with api.compile(cfg, mode="serve", backend="actors", stages=2,
                     cache="paged", page_len=4, num_pages=8, mesh=mesh,
                     num_groups=2, group_size=2, max_prompt_len=8,
                     max_new_tokens=4, cache_len=24) as sess:
        stages = sess.sstaged.stages
        B = sess.group_size
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)

        def name(lowered):
            return re.search(r"module @(\S+)", lowered.as_text())[1]

        li = jax.ShapeDtypeStruct((1,), jnp.int32)
        names, xin, pin = set(), pos, jax.ShapeDtypeStruct((1, 8), jnp.int32)
        for st in stages:
            caches = jax.eval_shape(st.init_caches, pos)
            names.add(name(st.decode.lower(st.params, caches, xin, pos)))
            names.add(name(st.prefill.lower(st.params, pin, li)))
            xin = jax.eval_shape(st.decode, st.params, caches, xin, pos)[0]
            pin = jax.eval_shape(st.prefill, st.params, pin, li)[0]
        cache = make_stage_cache(stages[0], B, sess.cache_len,
                                 sess.cache_spec)
        cache._ensure()
        rows = np.full((B, sess.cache_spec.pages_per_req), -1, np.int32)
        sids = np.full((B,), -1, np.int32)
        fns = cache._fns
        names.add(name(fns["gather"].lower(cache.slabs, rows, sids)))
        window = jax.eval_shape(fns["gather"], cache.slabs, rows, sids)
        names.add(name(fns["scatter_decode"].lower(
            cache.slabs, rows, sids, pos, window)))
    decode_modules = registry.metric_reader(
        "decode_roofline.batch").DECODE_MODULES
    matched = {n for n in names
               if any(re.search(p, n) for p in decode_modules)}
    assert names - matched == {"jit_local_prefill_s0", "jit_local_prefill_s1"}
    assert matched == {"jit_local_decode_s0", "jit_local_decode_s1",
                       "jit_gather", "jit_scatter_decode"}
