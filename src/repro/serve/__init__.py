"""Production-scale serving subsystem (ROADMAP: paged KV caches, sampling,
chunked prefill).

The dense serve path (PR 5) holds one ``(group_size, cache_len, ...)`` cache
block per slot group per stage — every admitted request reserves its
worst-case window whether it uses it or not. This package replaces that
reservation with the paper's register discipline applied to serving state:

* :mod:`repro.serve.paged_cache` — one preallocated page slab per stage
  (``(num_pages, page_len, ...)`` per KV tensor) plus an int32 page table
  and per-request cursors; alloc/free are host bookkeeping, gather/scatter
  are jitted fixed-shape programs, shared-prefix pages are refcounted.
* :mod:`repro.serve.sampler` — temperature/top-k/top-p sampling as an
  actor-borne RNG register stream (keys split per sampled work item), so
  sampled decode is reproducible and identical across the actors and
  monolithic backends.
* :mod:`repro.serve.admission` — the continuous-batching admission
  scheduler, including chunked prefill: long prompts become bounded work
  items interleaved with decode rounds.

Everything is reached through ``api.compile(cfg, mode="serve",
cache="paged", page_len=..., num_pages=..., sampling=...)``; the dense path
stays untouched as the bit-identity reference.
"""
from repro.serve.admission import AdmissionScheduler
from repro.serve.paged_cache import PagedCacheSpec, PagedStageCache, PagePool
from repro.serve.sampler import SamplerStream, SamplingSpec

__all__ = ["AdmissionScheduler", "PagedCacheSpec", "PagedStageCache",
           "PagePool", "SamplerStream", "SamplingSpec"]
