"""Paged-cache serving tests: the page pool, gather/scatter bit-identity
against the dense backend, chunked prefill, shared-prefix refcounting, the
analytic cache-bytes accounting, and the in-place GQA decode (the TPU's
path) against the gather path.

The dense PR-5 path is the bit-identity reference: greedy decode through
``cache="paged"`` must emit the exact token streams of ``cache="dense"``
on every backend/runtime combination, because a gathered page window
agrees with the dense group cache at every position a live request's
decode can observe.
"""
import dataclasses
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import api
from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
from repro.models.model_zoo import build_model
from repro.serve.paged_cache import PagePool, PagedCacheSpec
from repro.train.steps import plan_from_mesh

PROMPT_LEN = 8
GENS = [3, 6, 2, 5, 4]
CACHE_LEN = 24
PAGE_LEN = 4
NUM_PAGES = 8


@pytest.fixture(scope="module")
def serve_env():
    cfg = get_config("qwen2.5-3b").reduced()
    cfg = dataclasses.replace(cfg, vocab_size=1000)   # padded head columns
    mesh = make_mesh((1, 1), ("data", "model"))
    params = build_model(cfg, plan_from_mesh(mesh)).init(
        jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (PROMPT_LEN,)).astype(np.int32) for _ in GENS]
    return cfg, mesh, params, prompts


def _kw(params, mesh, **over):
    kw = dict(params=params, mesh=mesh, num_groups=2, group_size=1,
              max_prompt_len=PROMPT_LEN, max_new_tokens=max(GENS),
              cache_len=CACHE_LEN)
    kw.update(over)
    return kw


@pytest.fixture(scope="module")
def dense_ref(serve_env):
    """Dense monolithic greedy token streams: the bit-identity reference."""
    cfg, mesh, params, prompts = serve_env
    sess = api.compile(cfg, mode="serve", backend="monolithic",
                       **_kw(params, mesh))
    return sess.generate(list(zip(prompts, GENS)))


class TestPagePool:
    SPEC = PagedCacheSpec(page_len=4, num_pages=8, max_requests=4,
                          pages_per_req=6)

    def test_alloc_free_roundtrip(self):
        pool = PagePool(self.SPEC)
        row = pool.alloc(0, 3)
        assert (row >= 0).sum() == 3 and pool.free_count() == 5
        assert np.array_equal(pool.row(0), row)
        pool.free(0)
        assert pool.free_count() == 8
        assert (pool.page_table[0] == -1).all()

    def test_shared_pages_masked_in_write_row(self):
        pool = PagePool(self.SPEC)
        donor = pool.alloc(0, 2)
        row1 = pool.alloc(1, 1, shared=[int(donor[0])])
        # shared entry is mapped in the table but masked in the write row
        assert pool.page_table[1][0] == donor[0] and row1[0] == -1
        assert (row1 >= 0).sum() == 1
        assert pool.ref_counts[donor[0]] == 2

    def test_shared_pages_survive_donor_free(self):
        pool = PagePool(self.SPEC)
        donor = pool.alloc(0, 2)
        pool.alloc(1, 1, shared=[int(donor[0])])
        pool.free(0)
        # donor's private page returned, the shared one is still held
        assert pool.free_count() == 8 - 2
        assert pool.ref_counts[donor[0]] == 1
        pool.free(1)
        assert pool.free_count() == 8

    def test_double_alloc_and_exhaustion_raise(self):
        pool = PagePool(self.SPEC)
        pool.alloc(0, 3)
        with pytest.raises(ValueError, match="already mapped"):
            pool.alloc(0, 1)
        with pytest.raises(ValueError, match="exhausted"):
            pool.alloc(1, 6)          # <= pages_per_req but only 5 free
        with pytest.raises(ValueError, match="pages_per_req"):
            pool.alloc(2, 7)

    def test_rows_parks_negative_sids(self):
        pool = PagePool(self.SPEC)
        pool.alloc(2, 2)
        rows = pool.rows([-1, 2])
        assert (rows[0] == -1).all()
        assert np.array_equal(rows[1], pool.row(2))

    def test_peak_pages_tracks_high_water(self):
        pool = PagePool(self.SPEC)
        pool.alloc(0, 3)
        pool.alloc(1, 2)
        pool.free(0)
        assert pool.used_pages() == 2 and pool.peak_pages == 5


class TestPagedTokenIdentity:
    def test_monolithic_paged_matches_dense(self, serve_env, dense_ref):
        cfg, mesh, params, prompts = serve_env
        sess = api.compile(cfg, mode="serve", backend="monolithic",
                           cache="paged", page_len=PAGE_LEN,
                           num_pages=NUM_PAGES, **_kw(params, mesh))
        outs = sess.generate(list(zip(prompts, GENS)))
        for i, (got, ref) in enumerate(zip(outs, dense_ref)):
            assert np.array_equal(got, ref), f"request {i}: {got} != {ref}"
        stats = sess.last_stats
        assert 0 < stats["peak_pages"] <= NUM_PAGES
        assert "paged" in sess.describe()

    def test_actor_pipeline_paged_matches_dense(self, serve_env, dense_ref):
        cfg, mesh, params, prompts = serve_env
        with api.compile(cfg, mode="serve", backend="actors", stages=2,
                         cache="paged", page_len=PAGE_LEN,
                         num_pages=NUM_PAGES, **_kw(params, mesh)) as sess:
            outs = sess.generate(list(zip(prompts, GENS)))
        for i, (got, ref) in enumerate(zip(outs, dense_ref)):
            assert np.array_equal(got, ref), f"request {i}: {got} != {ref}"

    def test_ssm_paged_matches_dense(self):
        """Recurrent state (SSM h, conv tails) lives in the per-request row
        pool, not the page slabs; paged serving must still match dense."""
        cfg = get_config("mamba2-370m").reduced()
        mesh = make_mesh((1, 1), ("data", "model"))
        params = build_model(cfg, plan_from_mesh(mesh)).init(
            jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        reqs = [(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32), g)
                for n, g in ((5, 3), (8, 2), (6, 4))]
        kw = dict(params=params, mesh=mesh, num_groups=2, group_size=1,
                  max_prompt_len=8, max_new_tokens=4, cache_len=CACHE_LEN)
        ref = api.compile(cfg, mode="serve", backend="monolithic",
                          **kw).generate(reqs)
        with api.compile(cfg, mode="serve", backend="actors", stages=2,
                         cache="paged", page_len=4, num_pages=10,
                         **kw) as sess:
            outs = sess.generate(reqs)
        for i, (got, want) in enumerate(zip(outs, ref)):
            assert np.array_equal(got, want), f"ssm {i}: {got} != {want}"


class TestChunkedPrefill:
    def test_chunked_backends_agree(self, serve_env):
        """Chunked prefill is the same scan-of-decode program on every
        backend: monolithic and actor-pipeline streams must be identical,
        and prompts longer than the chunk land over multiple rounds."""
        cfg, mesh, params, prompts = serve_env
        kw = dict(cache="paged", page_len=PAGE_LEN, num_pages=NUM_PAGES,
                  prefill_chunk=3)
        mono = api.compile(cfg, mode="serve", backend="monolithic",
                           **kw, **_kw(params, mesh))
        a = mono.generate(list(zip(prompts, GENS)))
        with api.compile(cfg, mode="serve", backend="actors", stages=2,
                         **kw, **_kw(params, mesh)) as sess:
            b = sess.generate(list(zip(prompts, GENS)))
        for i, (x, y) in enumerate(zip(a, b)):
            assert np.array_equal(x, y), f"request {i}: {x} != {y}"
        assert [len(o) for o in a] == GENS
        assert all((o < cfg.vocab_size).all() and (o >= 0).all() for o in a)
        # 8-token prompts at chunk 3 need 3 chunk rounds before their first
        # token, so the session runs strictly more rounds than unchunked
        assert mono.last_stats["rounds"] > max(GENS) + 1

    def test_chunks_interleave_with_decode(self, serve_env):
        """A long prompt admitted mid-flight must not stall live decoding:
        rounds containing its chunks still carry decode work."""
        from repro.serve.admission import AdmissionScheduler
        from repro.serve.paged_cache import PagePool, PagedCacheSpec
        from repro.runtime.pipeline import DecodeWork, PrefillChunkWork

        spec = PagedCacheSpec(page_len=PAGE_LEN, num_pages=NUM_PAGES,
                              max_requests=2, pages_per_req=6)
        prompts = [np.arange(2, dtype=np.int32),
                   np.arange(8, dtype=np.int32)]
        sched = AdmissionScheduler(prompts, [6, 2], num_groups=2,
                                   group_size=1, cache_len=CACHE_LEN,
                                   pool=PagePool(spec), prefill_chunk=3)
        work, meta = sched.plan_round()     # prefill r0 + 1st chunk of r1
        kinds = [type(w).__name__ for w in work]
        assert kinds == ["PrefillWork", "PrefillChunkWork"]
        sched.absorb(meta[0], np.asarray([5]))
        sched.absorb(meta[1], None)
        work, meta = sched.plan_round()
        # r0 decodes in the same round as r1's second chunk
        assert {type(w).__name__ for w in work} == {"DecodeWork",
                                                    "PrefillChunkWork"}
        chunk = [w for w in work if isinstance(w, PrefillChunkWork)][0]
        assert not chunk.final and int(np.asarray(chunk.pos0)[0]) == 3

    def test_prefill_chunk_requires_paged(self, serve_env):
        cfg, mesh, params, _ = serve_env
        with pytest.raises(ValueError, match="prefill_chunk"):
            api.compile(cfg, mode="serve", prefill_chunk=3,
                        **_kw(params, mesh))


class TestSharedPrefix:
    def test_identical_prompts_share_pages(self, serve_env):
        """With a long-lived donor, later identical prompts map the
        page-aligned common prefix instead of re-storing it — and still
        emit the dense token streams."""
        cfg, mesh, params, prompts = serve_env
        reqs = [(prompts[0], 6), (prompts[0], 3), (prompts[0], 3),
                (prompts[0], 4)]
        dense = api.compile(cfg, mode="serve", backend="monolithic",
                            **_kw(params, mesh))
        ref = dense.generate(reqs)
        shr = api.compile(cfg, mode="serve", backend="monolithic",
                          cache="paged", page_len=PAGE_LEN, num_pages=16,
                          **_kw(params, mesh))
        outs = shr.generate(reqs)
        for i, (got, want) in enumerate(zip(outs, ref)):
            assert np.array_equal(got, want), f"request {i}"
        assert shr.last_stats["shared_pages"] > 0

    def test_disjoint_prompts_share_nothing(self, serve_env, dense_ref):
        cfg, mesh, params, prompts = serve_env
        sess = api.compile(cfg, mode="serve", backend="monolithic",
                           cache="paged", page_len=PAGE_LEN,
                           num_pages=NUM_PAGES, **_kw(params, mesh))
        outs = sess.generate(list(zip(prompts, GENS)))
        for got, want in zip(outs, dense_ref):
            assert np.array_equal(got, want)
        assert sess.last_stats["shared_pages"] == 0


class TestCacheBytes:
    def test_paged_pool_halves_cache_bytes(self, serve_env):
        """The headline arithmetic: at 4 slots, the paged pool sized for
        the realistic in-flight load holds under half the dense
        worst-case reservation."""
        cfg, mesh, params, _ = serve_env
        kw = dict(params=params, mesh=mesh, num_groups=2, group_size=2,
                  max_prompt_len=PROMPT_LEN, max_new_tokens=max(GENS),
                  cache_len=CACHE_LEN)
        dense = api.compile(cfg, mode="serve", backend="monolithic", **kw)
        paged = api.compile(cfg, mode="serve", backend="monolithic",
                            cache="paged", page_len=PAGE_LEN, num_pages=8,
                            **kw)
        assert paged.cache_bytes() * 2 <= dense.cache_bytes()

    def test_default_num_pages_matches_dense_capacity(self, serve_env):
        """Without num_pages=, the pool holds exactly the dense capacity
        (every slot at full cache_len) — same bytes, any length mix."""
        cfg, mesh, params, _ = serve_env
        sess = api.compile(cfg, mode="serve", backend="monolithic",
                           cache="paged", page_len=PAGE_LEN,
                           **_kw(params, mesh))
        spec = sess.cache_spec
        assert spec.num_pages * spec.page_len == 2 * 1 * CACHE_LEN


class TestPagedValidation:
    def test_page_len_must_divide_cache_len(self, serve_env):
        cfg, mesh, params, _ = serve_env
        with pytest.raises(ValueError, match="page_len"):
            api.compile(cfg, mode="serve", cache="paged", page_len=5,
                        **_kw(params, mesh))

    def test_pool_must_hold_one_worst_case_request(self, serve_env):
        cfg, mesh, params, _ = serve_env
        with pytest.raises(ValueError, match="num_pages"):
            api.compile(cfg, mode="serve", cache="paged", page_len=PAGE_LEN,
                        num_pages=2, **_kw(params, mesh))

    def test_paged_options_require_paged_cache(self, serve_env):
        cfg, mesh, params, _ = serve_env
        for bad in ({"page_len": 4}, {"num_pages": 8},
                    {"prefill_chunk": 3}):
            with pytest.raises(ValueError, match="cache='paged'"):
                api.compile(cfg, mode="serve", **bad, **_kw(params, mesh))

    def test_unknown_cache_kind(self, serve_env):
        cfg, mesh, params, _ = serve_env
        with pytest.raises(ValueError, match="dense.*paged|paged.*dense"):
            api.compile(cfg, mode="serve", cache="virtual",
                        **_kw(params, mesh))

    def test_spec_geometry_must_match_cache_len(self):
        from repro.serve.paged_cache import PagedStageCache

        spec = PagedCacheSpec(page_len=4, num_pages=8, max_requests=2,
                              pages_per_req=5)
        with pytest.raises(ValueError, match="cache_len"):
            PagedStageCache(stage=None, group_size=1, cache_len=24,
                            spec=spec)


class TestSlabDonation:
    """The scatter programs take the page slabs by donation: each updates
    the slabs in place and the caller's old slab leaves are dead after the
    call, so no program copies a whole slab to write a few positions."""

    @pytest.fixture(scope="class")
    def session(self, serve_env):
        cfg, mesh, params, _ = serve_env
        return api.compile(cfg, mode="serve", backend="monolithic",
                           cache="paged", page_len=PAGE_LEN,
                           num_pages=NUM_PAGES,
                           **_kw(params, mesh, group_size=2))

    @staticmethod
    def _call(cache, kind):
        """``(jitted program, its arguments)`` of one scatter kind, the
        slabs first, over a live slot 0 and a parked slot 1."""
        import jax.numpy as jnp

        spec, B = cache.spec, cache.group_size
        rows = np.full((B, spec.pages_per_req), -1, np.int32)
        rows[0] = np.arange(spec.pages_per_req)
        sids = np.array([0] + [-1] * (B - 1), np.int32)
        window = cache._fns["gather"](cache.slabs, rows, sids)
        window = jax.tree_util.tree_map(lambda x: x + 1, window)
        if kind == "decode":
            pos = np.array([5] + [CACHE_LEN - 1] * (B - 1), np.int32)
            return (cache._fns["scatter_decode"],
                    (cache.slabs, rows, sids, pos, window))
        if kind == "prefill":
            one = jax.eval_shape(cache.stage.init_caches,
                                 jnp.zeros((1,), jnp.int32))
            slot = jax.tree_util.tree_map(
                lambda s: jnp.ones(s.shape, s.dtype), one)
            return (cache._fns["scatter_prefill"],
                    (cache.slabs, rows[0], np.int32(0), slot))
        pos0 = np.array([2] + [CACHE_LEN - 1] * (B - 1), np.int32)
        adv = np.array([1] + [0] * (B - 1), np.int32)
        return (cache._fns["scatter_chunk"](3),
                (cache.slabs, rows, sids, pos0, adv, window))

    @pytest.mark.parametrize("kind", ["decode", "prefill", "chunk"])
    def test_scatter_donates_slabs(self, session, kind):
        from repro.runtime.pipeline import make_stage_cache

        cache = make_stage_cache(session.sstaged.stages[0],
                                 session.group_size, session.cache_len,
                                 session.cache_spec)
        cache._ensure()
        fn, args = self._call(cache, kind)
        leaves = jax.tree_util.tree_leaves(args[0])
        # the module's header line carries input_output_alias: output
        # index -> (parameter number, ...); the slab leaves come first
        header = fn.lower(*args).compile().as_text().splitlines()[0]
        aliased = {int(p) for p in
                   re.findall(r"\{[\d,]*\}: \((\d+), ", header)}
        assert aliased >= set(range(len(leaves))), header
        out = fn(*args)
        assert all(leaf.is_deleted() for leaf in leaves)
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree_util.tree_leaves(out))


class TestDonationOrdering:
    @pytest.mark.parametrize("backend", ["monolithic", "actors"])
    def test_interleaved_stage_steps_match_dense(self, serve_env, backend):
        """Every scatter donates the slabs the stage's gather read earlier
        in the same fire, and the next fire gathers from the scatter's
        output. Two slot groups on a stage, a whole-prompt prefill scatter
        between their decodes, then chunked prefills: no fire may touch a
        donated buffer, and the greedy tokens stay the dense path's."""
        from repro.serve.paged_cache import PagedStageCache

        cfg, mesh, params, prompts = serve_env
        rng = np.random.default_rng(3)
        long = [rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
                for _ in range(2)]
        reqs = [(prompts[0], 2), (prompts[1], 5), (prompts[2], 3),
                (prompts[3], 6), (prompts[4], 3), (long[0], 3),
                (long[1], 4)]
        kw = _kw(params, mesh, group_size=2, max_prompt_len=12,
                 cache_len=20)
        ref = api.compile(cfg, mode="serve", backend="monolithic",
                          **kw).generate(reqs)
        steps = []

        def logged(name):
            orig = getattr(PagedStageCache, name)

            def run(self, work, *a):
                if self.stage.index == 0:
                    steps.append((name, work.group))
                return orig(self, work, *a)
            return run

        with pytest.MonkeyPatch.context() as mp:
            for name in ("run_decode", "write_prefill", "run_chunk"):
                mp.setattr(PagedStageCache, name, logged(name))
            with api.compile(cfg, mode="serve", backend=backend,
                             stages=1 if backend == "monolithic" else 2,
                             cache="paged", page_len=PAGE_LEN,
                             num_pages=20, prefill_chunk=8, **kw) as sess:
                outs = sess.generate(reqs)
        for i, (got, want) in enumerate(zip(outs, ref)):
            assert np.array_equal(got, want), f"request {i}: {got} != {want}"
        # a prefill scatter between the decodes of the two groups ...
        assert any(a[0] == c[0] == "run_decode" and a[1] != c[1]
                   and b[0] == "write_prefill"
                   for a, b, c in zip(steps, steps[1:], steps[2:]))
        # ... and chunked prefills after decodes have run
        first_decode = steps.index(("run_decode", 0))
        assert any(s[0] == "run_chunk" for s in steps[first_decode:])


class TestSlabsReadInPlace:
    """On the TPU a GQA stage's paged decode reads the page slabs in place
    through the table rows (``ServeStage.decode_slabs``, the Pallas paged
    flash-decode) and returns only the new positions; every other stage
    gathers a dense window. Here the kernel runs in interpret mode on the
    CPU, where a stage would otherwise gather."""

    @staticmethod
    def _forced(mp):
        import functools

        from repro.core import lowering
        from repro.kernels.flash_decode.kernel import \
            flash_decode_paged_pallas

        mp.setattr(lowering, "paged_decode_kernel", lambda platform:
                   functools.partial(flash_decode_paged_pallas,
                                     interpret=True))

    @staticmethod
    def _run(sess, reqs):
        """Generate, keeping every round's work and results."""
        rounds = []
        run_round = sess.executor.run_round

        def logged(work, **kw):
            out = run_round(work, **kw)
            rounds.append((list(work), out))
            return out
        sess.executor.run_round = logged
        return sess.generate(reqs), rounds

    @pytest.mark.parametrize("backend", ["monolithic", "actors"])
    def test_in_place_matches_gathered(self, serve_env, backend):
        """Over a multi-round ``generate`` (two slot groups, admission mid
        flight, chunked prefills between decodes) the in-place path gives
        the gather path's greedy tokens, and every round's logits within
        float32 rounding. ``decode_fires_paged`` counts every stage decode
        fire of the in-place session, ``decode_fires_gathered`` every one
        of the other."""
        from repro.runtime.pipeline import DecodeWork

        cfg, mesh, params, prompts = serve_env
        rng = np.random.default_rng(5)
        long = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
        reqs = list(zip(prompts, GENS)) + [(long, 4)]
        stages = 1 if backend == "monolithic" else 2
        kw = dict(backend=backend, stages=stages, cache="paged",
                  page_len=PAGE_LEN, prefill_chunk=8,
                  **_kw(params, mesh, group_size=2, max_prompt_len=12,
                        cache_len=20))
        runs = {}
        for path in ("gathered", "paged"):
            with pytest.MonkeyPatch.context() as mp:
                if path == "paged":
                    self._forced(mp)
                sess = api.compile(cfg, mode="serve", **kw)
            with sess:
                assert all((st.decode_slabs is not None) == (path == "paged")
                           for st in sess.sstaged.stages)
                runs[path] = self._run(sess, reqs) + (dict(sess.last_stats),)
        (want, want_rounds, want_stats), (got, got_rounds, stats) = (
            runs["gathered"], runs["paged"])
        for i, (g, w) in enumerate(zip(got, want)):
            assert np.array_equal(g, w), f"request {i}: {g} != {w}"
        assert len(got_rounds) == len(want_rounds) > 3
        for (work, outs), (_, ref) in zip(got_rounds, want_rounds):
            for w, o, r in zip(work, outs, ref):
                # a parked slot's logits are never read: live slots only
                live = (np.asarray(w.sids) >= 0
                        if isinstance(w, DecodeWork) else slice(None))
                np.testing.assert_allclose(np.asarray(o)[live],
                                           np.asarray(r)[live],
                                           rtol=1e-4, atol=1e-4)
        fires = stages * sum(isinstance(w, DecodeWork)
                             for work, _ in got_rounds for w in work)
        assert fires > 0
        assert (stats["decode_fires_paged"],
                stats["decode_fires_gathered"]) == (fires, 0)
        assert (want_stats["decode_fires_paged"],
                want_stats["decode_fires_gathered"]) == (0, fires)

    def test_prologue_and_expert_stack_in_place(self):
        """A GQA stack with a dense prologue layer and expert layers (a
        DeepSeek-shaped stack without latent attention) reads the slabs in
        place on both stages: the prologue's unstacked slab and the body's
        stacked one give the gather path's tokens, and the expert counters
        of the call are the gather path's."""
        cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").reduced(),
                                  use_mla=False, num_layers=3)
        mesh = make_mesh((1, 1), ("data", "model"))
        params = build_model(cfg, plan_from_mesh(mesh)).init(
            jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        reqs = [(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32), g)
                for n, g in ((6, 4), (8, 3), (5, 4))]
        kw = dict(backend="actors", stages=2, cache="paged",
                  page_len=PAGE_LEN, params=params, mesh=mesh, num_groups=1,
                  group_size=2, max_prompt_len=8, max_new_tokens=4,
                  cache_len=CACHE_LEN)
        outs, stats = [], []
        for forced in (False, True):
            with pytest.MonkeyPatch.context() as mp:
                if forced:
                    self._forced(mp)
                sess = api.compile(cfg, mode="serve", **kw)
            with sess:
                assert [st.decode_slabs is not None
                        for st in sess.sstaged.stages] == [forced] * 2
                assert len(sess.sstaged.stages[0].params["prologue"]) == 1
                assert any(st.moe for st in sess.sstaged.stages)
                outs.append(sess.generate(reqs))
                stats.append(dict(sess.last_stats))
        for i, (g, w) in enumerate(zip(outs[1], outs[0])):
            assert np.array_equal(g, w), f"request {i}: {g} != {w}"
        moe = [k for k in stats[0] if k.startswith("moe_")]
        assert moe and all(stats[1][k] == stats[0][k] for k in moe)
        assert stats[1]["decode_fires_paged"] == stats[0][
            "decode_fires_gathered"] > 0

    def test_latent_attention_always_gathers(self):
        """MLA's latent cache has no paged kernel: with the kernel on hand,
        a DeepSeek-shaped stack still builds no in-place program and every
        decode fire gathers."""
        cfg = get_config("deepseek-v2-lite-16b").reduced()
        mesh = make_mesh((1, 1), ("data", "model"))
        params = build_model(cfg, plan_from_mesh(mesh)).init(
            jax.random.PRNGKey(0))
        rng = np.random.default_rng(6)
        reqs = [(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32), g)
                for n, g in ((6, 3), (8, 2))]
        with pytest.MonkeyPatch.context() as mp:
            self._forced(mp)
            sess = api.compile(cfg, mode="serve", backend="monolithic",
                               cache="paged", page_len=PAGE_LEN,
                               params=params, mesh=mesh, num_groups=1,
                               group_size=2, max_prompt_len=8,
                               max_new_tokens=3, cache_len=CACHE_LEN)
        assert all(st.decode_slabs is None for st in sess.sstaged.stages)
        sess.generate(reqs)
        assert sess.last_stats["decode_fires_paged"] == 0
        assert sess.last_stats["decode_fires_gathered"] > 0
