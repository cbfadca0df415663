"""The program's profiler spans: a traced tiny two-stage paged serving
session writes every span of the serve loop, the actor runtime and the
stage fires into the profiler's trace, nested and linked as the benchmark's
span reduction reads them."""
import collections
import dataclasses
import glob

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import api
from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
from repro.models.model_zoo import build_model
from repro.train.steps import plan_from_mesh

PREFIXES = ("serve.", "runtime.", "actor.", "stage.")
SPANS = {"serve.generate", "serve.plan", "serve.absorb", "runtime.round",
         "runtime.epoch_start", "runtime.epoch_end", "actor.fire",
         "stage.gather", "stage.program", "stage.sync", "stage.scatter"}
GENS = [3, 6, 2, 5, 4]


def _program_spans(trace_dir):
    """(name, start_ns, end_ns, thread, stats) of every program span."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                (plane.name, i), dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A warmed-up 2-stage paged actor session, its second ``generate``
    traced: (spans, session history of that call)."""
    cfg = dataclasses.replace(get_config("qwen2.5-3b").reduced(),
                              vocab_size=1000)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = build_model(cfg, plan_from_mesh(mesh)).init(
        jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32), g)
            for g in GENS]
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with api.compile(cfg, mode="serve", backend="actors", stages=2,
                     cache="paged", page_len=4, num_pages=8, params=params,
                     mesh=mesh, num_groups=2, group_size=1,
                     max_prompt_len=8, max_new_tokens=max(GENS),
                     cache_len=24) as sess:
        sess.generate(reqs)                     # compiles outside the trace
        h0 = len(sess.history)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            sess.generate(reqs)
        finally:
            jax.profiler.stop_trace()
        history = sess.history[h0:]
    return _program_spans(trace_dir), history


def test_every_span_is_written(traced):
    spans, history = traced
    assert {s[0] for s in spans} == SPANS
    rounds = [h for h in history if h["kind"] == "round"]
    by_name = collections.Counter(s[0] for s in spans)
    assert by_name["serve.generate"] == 1
    assert by_name["serve.plan"] == by_name["serve.absorb"] == len(rounds)
    assert by_name["runtime.round"] == len(rounds)
    assert by_name["runtime.epoch_start"] == len(rounds)
    assert by_name["runtime.epoch_end"] == len(rounds)
    # metadata only where the span reduction reads it
    for name, _, _, _, stats in spans:
        if name == "actor.fire":
            assert set(stats) == {"actor", "epoch", "fire"}
        elif name.startswith("stage."):
            assert set(stats) == {"kind"}
        else:
            assert stats == {}, name


def test_one_fire_span_per_actor_fire(traced):
    """Each round's items go through ``admit`` and both stages: items x
    (stages + 1) fires per round, each with its actor, epoch and version."""
    spans, history = traced
    fires = [s for s in spans if s[0] == "actor.fire"]
    items = sum(h["items"] for h in history if h["kind"] == "round")
    assert len(fires) == items * 3
    per_actor = collections.Counter(s[4]["actor"] for s in fires)
    assert per_actor == {"admit": items, "stage0": items, "stage1": items}
    rounds = [h["items"] for h in history if h["kind"] == "round"]
    epochs = sorted({s[4]["epoch"] for s in fires})
    assert len(epochs) == len(rounds)
    for e, n in zip(epochs, rounds):
        for actor in ("admit", "stage0", "stage1"):
            ks = sorted(s[4]["fire"] for s in fires
                        if s[4]["epoch"] == e and s[4]["actor"] == actor)
            assert ks == list(range(n))


def test_stage_spans_nest_in_a_fire_on_their_thread(traced):
    spans, _ = traced
    fires = [s for s in spans if s[0] == "actor.fire"]
    kinds = collections.Counter()
    for name, a, b, thread, stats in spans:
        if not name.startswith("stage."):
            continue
        owners = [f for f in fires
                  if f[3] == thread and f[1] <= a and b <= f[2]]
        assert len(owners) == 1, (name, stats)
        assert owners[0][4]["actor"] in ("stage0", "stage1")
        kinds[name, stats["kind"]] += 1
    # paged decode: gather, program, sync, scatter; prefill: program,
    # sync, scatter
    assert kinds["stage.gather", "decode"] == kinds["stage.sync", "decode"]
    assert kinds["stage.scatter", "prefill"] == kinds["stage.sync",
                                                       "prefill"] > 0


def test_epoch_and_fire_link_the_stages(traced):
    """Fire k of stage0 and fire k of stage1 in one epoch are the same work
    item: stage1 starts it after stage0 finished it, on another thread."""
    spans, _ = traced
    fires = {(s[4]["actor"], s[4]["epoch"], s[4]["fire"]): s
             for s in spans if s[0] == "actor.fire"}
    linked = 0
    for (actor, e, k), s1 in fires.items():
        if actor != "stage1":
            continue
        s0 = fires[("stage0", e, k)]
        assert s0[2] <= s1[1] and s0[3] != s1[3]
        linked += 1
    assert linked == sum(1 for key in fires if key[0] == "stage0")


def test_round_span_covers_its_epoch(traced):
    """Round ``i`` holds the ``i``-th epoch: its start and end spans and
    every fire of that epoch."""
    spans, _ = traced

    def ordered(name):
        return sorted((s for s in spans if s[0] == name), key=lambda s: s[1])

    rounds = ordered("runtime.round")
    starts, ends = ordered("runtime.epoch_start"), ordered("runtime.epoch_end")
    fires = ordered("actor.fire")
    epochs = sorted({f[4]["epoch"] for f in fires})
    assert len(rounds) == len(starts) == len(ends) == len(epochs)
    for r, a, b, e in zip(rounds, starts, ends, epochs):
        assert r[1] <= a[1] and a[2] <= b[1] and b[2] <= r[2]
        for f in fires:
            if f[4]["epoch"] == e:
                assert a[1] <= f[1] and f[2] <= b[2]


def test_monolithic_engine_writes_round_and_stage_spans(tmp_path):
    """The inline engine shares the stage caches: its rounds and stage
    spans are written too, with no actor fire around them."""
    cfg = dataclasses.replace(get_config("qwen2.5-3b").reduced(),
                              vocab_size=1000)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = build_model(cfg, plan_from_mesh(mesh)).init(
        jax.random.PRNGKey(0))
    sess = api.compile(cfg, mode="serve", backend="monolithic",
                       params=params, mesh=mesh, num_groups=1,
                       group_size=1, max_prompt_len=8, max_new_tokens=3,
                       cache_len=16)
    req = [(np.arange(8, dtype=np.int32), 3)]
    sess.generate(req)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sess.generate(req)
    finally:
        jax.profiler.stop_trace()
    names = collections.Counter(s[0] for s in _program_spans(str(tmp_path)))
    assert "actor.fire" not in names and "runtime.epoch_start" not in names
    # one prefill round, then two decode rounds (dense cache: no gather)
    assert names["runtime.round"] == 3
    assert names["stage.program"] == names["stage.sync"] == 3
    assert names["stage.scatter"] == 1 and "stage.gather" not in names
