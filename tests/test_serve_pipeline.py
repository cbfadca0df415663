"""Serving-path tests: continuous-batching decode on the actor pipeline.

The reference semantics is the monolithic ``make_serve_step`` loop (one
batched prefill + whole-stack greedy decode). The pipelined ``ServeSession``
packs the same requests into decode slots, retires/admits mid-flight, and
must emit token-identical generations — including over a padded vocabulary
(vocab_size=1000 pads to 1024 logit columns) and requests with unequal
generation lengths.
"""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro import api
from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
from repro.models.model_zoo import build_model
from repro.train.steps import (greedy_from_logits, make_serve_step,
                               plan_from_mesh)

PROMPT_LEN = 8
GENS = [3, 6, 2, 5, 4]          # unequal generation lengths
CACHE_LEN = 24


@pytest.fixture(scope="module")
def serve_env():
    cfg = get_config("qwen2.5-3b").reduced()
    # vocab 1000 pads to 1024: the head emits 24 junk logit columns that
    # greedy selection must never pick
    cfg = dataclasses.replace(cfg, vocab_size=1000)
    assert cfg.padded_vocab() > cfg.vocab_size
    mesh = make_mesh((1, 1), ("data", "model"))
    params = build_model(cfg, plan_from_mesh(mesh)).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (PROMPT_LEN,)).astype(np.int32)
               for _ in GENS]
    return cfg, mesh, params, prompts


@pytest.fixture(scope="module")
def reference_tokens(serve_env):
    """The monolithic make_serve_step loop over the fixed request set: one
    batched prefill, greedy decode to the longest request, per-request
    truncation. First-token logits go through logits_fn (the decode head)."""
    cfg, mesh, params, prompts = serve_env
    ss = make_serve_step(cfg, mesh, cache_len=CACHE_LEN)
    tokens = jnp.asarray(np.stack(prompts), jnp.int32)
    h_last, caches = ss.prefill_fn(params, {"tokens": tokens})
    tok = greedy_from_logits(ss.logits_fn(params, h_last), cfg.vocab_size)
    rows = [np.asarray(tok)]
    pos = jnp.full((len(GENS),), PROMPT_LEN, jnp.int32)
    for _ in range(max(GENS) - 1):
        logits, caches = ss.decode_fn(params, caches, tok, pos)
        tok = greedy_from_logits(logits, cfg.vocab_size)
        rows.append(np.asarray(tok))
        pos = pos + 1
    mat = np.stack(rows, 1)
    return [mat[i, :g] for i, g in enumerate(GENS)]


@pytest.fixture(scope="module")
def actor_session(serve_env):
    cfg, mesh, params, _ = serve_env
    return api.compile(cfg, mode="serve", backend="actors", stages=2,
                       params=params, mesh=mesh, num_groups=2, group_size=1,
                       max_prompt_len=PROMPT_LEN, max_new_tokens=max(GENS),
                       cache_len=CACHE_LEN)


@pytest.fixture(scope="module")
def mono_session(serve_env):
    cfg, mesh, params, _ = serve_env
    return api.compile(cfg, mode="serve", backend="monolithic",
                       params=params, mesh=mesh, num_groups=2, group_size=1,
                       max_prompt_len=PROMPT_LEN, max_new_tokens=max(GENS),
                       cache_len=CACHE_LEN)


class TestTokenIdentity:
    def test_pipeline_matches_monolithic_loop(self, serve_env, actor_session,
                                              reference_tokens):
        """5 requests through 2 slots: retirement + mid-flight admission,
        token-identical to the monolithic serve loop."""
        cfg, _, _, prompts = serve_env
        outs = actor_session.generate(list(zip(prompts, GENS)))
        assert [len(o) for o in outs] == GENS
        for i, (got, ref) in enumerate(zip(outs, reference_tokens)):
            assert np.array_equal(got, ref), (
                f"request {i}: pipeline {got} != monolithic loop {ref}")
        stats = actor_session.last_stats
        assert stats["admitted_mid_flight"] >= 1
        assert stats["tokens"] == sum(GENS)
        # padded-vocab columns never leak into the output
        assert all((o >= 0).all() and (o < cfg.vocab_size).all()
                   for o in outs)

    def test_monolithic_backend_matches_loop(self, serve_env, mono_session,
                                             reference_tokens):
        cfg, _, _, prompts = serve_env
        outs = mono_session.generate(list(zip(prompts, GENS)))
        for got, ref in zip(outs, reference_tokens):
            assert np.array_equal(got, ref)
        assert mono_session.last_stats["admitted_mid_flight"] >= 1

    def test_unequal_prompt_lengths_backends_agree(self, serve_env,
                                                   actor_session,
                                                   mono_session):
        """Prompts of different lengths run at their natural length (one
        prefill specialization each); the two backends must agree on every
        token."""
        cfg, _, _, prompts = serve_env
        reqs = [(prompts[0][:5], 3), (prompts[1], 4), (prompts[2][:7], 2)]
        a = actor_session.generate(reqs)
        b = mono_session.generate(reqs)
        for got, ref in zip(a, b):
            assert np.array_equal(got, ref)
        assert all((o < cfg.vocab_size).all() for o in a)

    def test_history_and_describe(self, actor_session):
        rep = actor_session.describe()
        assert "mode=serve" in rep and "backend=actors" in rep
        assert "stage 0" in rep and "stage 1" in rep
        kinds = {h["kind"] for h in actor_session.history}
        assert kinds == {"round", "generate"}


class TestSSMServe:
    def test_ssm_unequal_prompt_lengths_match_loop(self):
        """Recurrent SSM state makes prompt padding a correctness hazard
        (padding tokens would flow through the recurrence): prompts must run
        at their natural length. Each request is checked against its own
        monolithic B=1 serve loop."""
        cfg = get_config("mamba2-370m").reduced()
        mesh = make_mesh((1, 1), ("data", "model"))
        params = build_model(cfg, plan_from_mesh(mesh)).init(
            jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        reqs = [(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32), g)
                for n, g in ((5, 3), (8, 2))]

        ss = make_serve_step(cfg, mesh, cache_len=CACHE_LEN)
        ref = []
        for prompt, gen in reqs:
            h_last, caches = ss.prefill_fn(params, {"tokens": prompt[None]})
            tok = greedy_from_logits(ss.logits_fn(params, h_last),
                                     cfg.vocab_size)
            toks = [int(tok[0])]
            pos = jnp.asarray([prompt.size], jnp.int32)
            for _ in range(gen - 1):
                logits, caches = ss.decode_fn(params, caches, tok, pos)
                tok = greedy_from_logits(logits, cfg.vocab_size)
                toks.append(int(tok[0]))
                pos = pos + 1
            ref.append(np.asarray(toks, np.int32))

        sess = api.compile(cfg, mode="serve", backend="actors",
                           params=params, mesh=mesh, num_groups=2,
                           group_size=1, max_prompt_len=8,
                           max_new_tokens=3, cache_len=CACHE_LEN)
        outs = sess.generate(reqs)
        for i, (got, want) in enumerate(zip(outs, ref)):
            assert np.array_equal(got, want), (
                f"ssm request {i}: {got} != {want}")


class TestGreedyHead:
    def test_greedy_masks_padded_vocab(self):
        """argmax over raw padded logits can emit junk ids >= vocab_size;
        greedy_from_logits must never."""
        V, Vp = 1000, 1024
        logits = np.zeros((3, Vp), np.float32)
        logits[:, 1010] = 5.0          # junk column wins the raw argmax
        logits[:, 7] = 1.0
        raw = np.asarray(jnp.argmax(jnp.asarray(logits), -1))
        assert (raw >= V).all()
        masked = np.asarray(greedy_from_logits(logits, V))
        assert (masked == 7).all()

    def test_prefill_logits_through_decode_head(self, serve_env):
        """ServeStep.logits_fn is the decode-step head: same math, same
        dtype, same model-sharded output — not a host-side h @ unembed."""
        cfg, mesh, params, prompts = serve_env
        ss = make_serve_step(cfg, mesh, cache_len=CACHE_LEN)
        tokens = jnp.asarray(np.stack(prompts), jnp.int32)
        h_last, caches = ss.prefill_fn(params, {"tokens": tokens})
        logits0 = ss.logits_fn(params, h_last)
        assert logits0.shape == (len(prompts), cfg.padded_vocab())
        # decode-step logits for the next position have the same dtype and
        # shape — the two heads are the same program modulo the input token
        tok = greedy_from_logits(logits0, cfg.vocab_size)
        pos = jnp.full((len(prompts),), PROMPT_LEN, jnp.int32)
        logits1, _ = ss.decode_fn(params, caches, tok, pos)
        assert logits1.dtype == logits0.dtype
        assert logits1.shape == logits0.shape
        # and it matches the explicit head math bit for bit
        want = h_last[:, 0] @ params["unembed"].astype(h_last.dtype)
        assert np.array_equal(np.asarray(logits0), np.asarray(want))


class TestServeValidation:
    def test_serve_rejects_graph_mode_options(self, serve_env):
        cfg, mesh, params, _ = serve_env
        from repro.core.lowering import OptimizerSpec
        with pytest.raises(ValueError, match="optimizer"):
            api.compile(cfg, mode="serve", optimizer=OptimizerSpec.sgd())
        with pytest.raises(ValueError, match="num_microbatches"):
            api.compile(cfg, mode="serve", num_microbatches=4)

    def test_graph_modes_reject_serve_options(self):
        from repro.core.placement import Placement
        from repro.core.graph import LogicalGraph
        placement = Placement(("d",), (1,), device_kind="cpu")
        g = LogicalGraph(placement)
        x = g.input("x", (4, 4))
        w = g.input("w", (4, 4))
        g.matmul(x, w, name="mm")
        with pytest.raises(ValueError, match="group_size"):
            api.compile(g, mode="infer", backend="monolithic", group_size=2)

    def test_serve_needs_token_frontend(self):
        with pytest.raises(ValueError, match="token frontend"):
            api.compile(get_config("pixtral-12b").reduced(), mode="serve")
        with pytest.raises(ValueError, match="token frontend"):
            api.compile(get_config("whisper-medium").reduced(), mode="serve")

    def test_serve_rejects_bad_geometry(self, serve_env):
        cfg, mesh, params, _ = serve_env
        with pytest.raises(ValueError, match="cache_len"):
            api.compile(cfg, mode="serve", max_prompt_len=8,
                        max_new_tokens=8, cache_len=16)
        with pytest.raises(ValueError, match="num_stages"):
            api.compile(cfg, mode="serve", stages=99, params=params,
                        mesh=mesh)
        with pytest.raises(ValueError, match="whole stack"):
            api.compile(cfg, mode="serve", backend="monolithic", stages=2)

    def test_zero_quota_fails_fast(self, serve_env):
        cfg, mesh, params, _ = serve_env
        with pytest.raises(ValueError, match=r"stage 1 .* got 0"):
            api.compile(cfg, mode="serve", backend="actors", stages=2,
                        params=params, mesh=mesh, regs=[1, 0],
                        max_prompt_len=PROMPT_LEN,
                        max_new_tokens=2, cache_len=CACHE_LEN)

    def test_generate_validates_requests(self, actor_session):
        with pytest.raises(ValueError, match="prompt length"):
            actor_session.generate(
                [(np.zeros(PROMPT_LEN + 1, np.int32), 1)])
        with pytest.raises(ValueError, match="max_new_tokens"):
            actor_session.generate(
                [(np.zeros(4, np.int32), max(GENS) + 1)])
        with pytest.raises(ValueError, match="non-empty"):
            actor_session.generate([(np.zeros(0, np.int32), 1)])


class TestAdmissionEdgeCases:
    def test_empty_request_list(self, mono_session):
        outs = mono_session.generate([])
        assert outs == []
        assert mono_session.last_stats["requests"] == 0
        assert mono_session.last_stats["tokens"] == 0

    def test_more_requests_than_slots(self, serve_env, actor_session,
                                      mono_session):
        """6 requests over 2 decode slots: everything beyond the first two
        waits in the admission queue and lands mid-flight, FIFO."""
        cfg, mesh, params, prompts = serve_env
        reqs = [(prompts[i % len(prompts)], 2 + i % 3) for i in range(6)]
        a = actor_session.generate(reqs)
        b = mono_session.generate(reqs)
        assert [len(o) for o in a] == [2 + i % 3 for i in range(6)]
        for i, (x, y) in enumerate(zip(a, b)):
            assert np.array_equal(x, y), f"request {i}: {x} != {y}"
        assert mono_session.last_stats["admitted_mid_flight"] == 4

    def test_prompt_exactly_max_prompt_len(self, serve_env, mono_session):
        """The boundary length is admissible; one past it is not (the
        rejection is covered in TestServeValidation)."""
        cfg, mesh, params, prompts = serve_env
        assert prompts[0].size == mono_session.max_prompt_len
        outs = mono_session.generate([(prompts[0], 3)])
        assert len(outs) == 1 and outs[0].shape == (3,)

    def test_all_requests_retire_same_round(self, serve_env, actor_session,
                                            mono_session):
        """Both slots retire in the same round; the scheduler must drain
        cleanly with nothing left to admit."""
        cfg, mesh, params, prompts = serve_env
        reqs = [(prompts[0], 3), (prompts[1], 3)]
        a = actor_session.generate(reqs)
        b = mono_session.generate(reqs)
        for x, y in zip(a, b):
            assert np.array_equal(x, y)
        assert [len(o) for o in a] == [3, 3]
        assert mono_session.last_stats["admitted_mid_flight"] == 0


class TestSamplerStream:
    def _spec(self, **over):
        from repro.serve import SamplingSpec
        kw = dict(temperature=0.8, top_k=50, top_p=0.95, seed=7)
        kw.update(over)
        return SamplingSpec(**kw)

    def _session(self, serve_env, **over):
        cfg, mesh, params, _ = serve_env
        kw = dict(params=params, mesh=mesh, num_groups=2, group_size=1,
                  max_prompt_len=PROMPT_LEN, max_new_tokens=max(GENS),
                  cache_len=CACHE_LEN)
        kw.update(over)
        return api.compile(cfg, mode="serve", **kw)

    def test_temperature_zero_is_bitwise_greedy(self, serve_env,
                                                mono_session):
        """temperature=0 routes through greedy_from_logits itself, so the
        stream is bit-identical to the unsampled session."""
        cfg, mesh, params, prompts = serve_env
        reqs = list(zip(prompts, GENS))
        sess = self._session(serve_env, backend="monolithic",
                             sampling=self._spec(temperature=0))
        got = sess.generate(reqs)
        want = mono_session.generate(reqs)
        for i, (x, y) in enumerate(zip(got, want)):
            assert np.array_equal(x, y), f"request {i}: {x} != {y}"

    def test_fixed_seed_actors_match_monolithic(self, serve_env):
        """One RNG register stream keyed only by round order and slot id:
        the actor pipeline must replay the monolithic stream exactly."""
        cfg, mesh, params, prompts = serve_env
        reqs = list(zip(prompts, GENS))
        mono = self._session(serve_env, backend="monolithic",
                             sampling=self._spec())
        want = mono.generate(reqs)
        with self._session(serve_env, backend="actors", stages=2,
                           sampling=self._spec()) as sess:
            got = sess.generate(reqs)
        for i, (x, y) in enumerate(zip(got, want)):
            assert np.array_equal(x, y), f"request {i}: {x} != {y}"
        assert all((o >= 0).all() and (o < cfg.vocab_size).all()
                   for o in want)
        # a different seed must change at least one stream
        other = self._session(serve_env, backend="monolithic",
                              sampling=self._spec(seed=8)).generate(reqs)
        assert any(not np.array_equal(x, y) for x, y in zip(want, other))

    def test_sampling_spec_validation(self, serve_env):
        from repro.serve import SamplingSpec
        with pytest.raises(ValueError, match="temperature"):
            SamplingSpec(temperature=-0.5)
        with pytest.raises(ValueError, match="top_k"):
            SamplingSpec(top_k=-1)
        with pytest.raises(ValueError, match="top_p"):
            SamplingSpec(top_p=0.0)
        cfg, mesh, params, _ = serve_env
        with pytest.raises(ValueError, match="SamplingSpec"):
            self._session(serve_env, sampling="nucleus")


class TestServeOptionValidation:
    def test_geometry_error_names_all_three_options(self, serve_env):
        """Satellite: the compile-time budget check must name every knob
        the user could turn."""
        cfg, mesh, params, _ = serve_env
        with pytest.raises(ValueError) as e:
            api.compile(cfg, mode="serve", max_prompt_len=12,
                        max_new_tokens=12, cache_len=24)
        msg = str(e.value)
        for name in ("max_prompt_len", "max_new_tokens", "cache_len"):
            assert name in msg, f"{name!r} missing from: {msg}"

    def test_tiny_cache_len_names_parking_slot(self, serve_env):
        """cache_len < 2 leaves no room for the parking position
        (cache_len - 1); the lowering error says so explicitly."""
        cfg, mesh, params, _ = serve_env
        from repro.core.lowering import lower_serve_stages
        with pytest.raises(ValueError, match="parking"):
            lower_serve_stages(cfg, mesh, params, num_stages=1,
                               cache_len=1, max_prompt_len=1, group_size=1)
