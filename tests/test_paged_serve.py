"""Paged-cache serving tests: the page pool, gather/scatter bit-identity
against the dense backend, chunked prefill, shared-prefix refcounting, and
the analytic cache-bytes accounting.

The dense PR-5 path is the bit-identity reference: greedy decode through
``cache="paged"`` must emit the exact token streams of ``cache="dense"``
on every backend/runtime combination, because a gathered page window
agrees with the dense group cache at every position a live request's
decode can observe.
"""
import dataclasses

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

    def test_process_runtime_paged_matches_dense(self, serve_env, dense_ref):
        """The page-table rows ride the work items and the slabs live in
        the stage worker processes — the pool itself never crosses a
        process boundary."""
        cfg, mesh, params, prompts = serve_env
        with api.compile(cfg, mode="serve", backend="actors", stages=2,
                         runtime="processes", cache="paged",
                         page_len=PAGE_LEN, num_pages=NUM_PAGES,
                         **_kw(params, mesh)) as sess:
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
