"""Paged KV/SSM cache pool: the thin-stack trick applied to serving state.

The dense serve path allocates one ``(group_size, cache_len, ...)`` cache
block per slot group per stage — a request occupying a slot reserves its
worst-case decode window for its whole lifetime. This module replaces that
reservation with the paper's preallocated-register discipline:

* **One page slab per stage.** Every *positional* cache tensor (GQA
  ``k``/``v``, MLA ``c``/``kpe``) is stored as a fixed
  ``(num_pages, page_len, *feat)`` slab, allocated once. A request's cache
  window is a sequence of pages named by an int32 **page table** row
  ``(pages_per_req,)``; entry ``-1`` means unmapped. Non-positional
  per-request state (SSM ``h``, conv tails) lives in a
  ``(max_requests, *feat)`` row pool indexed by slot id.
* **Host plans, device executes.** Page allocation/free/refcounting is
  host-side numpy bookkeeping (:class:`PagePool`); the stage only runs
  jitted fixed-shape programs, so one stage program serves any mix of
  request lengths. A stage whose cache holds only GQA ``k``/``v`` (tp = 1,
  no sliding window) on the TPU decodes with ``ServeStage.decode_slabs``:
  a paged flash-decode reads each live slot's pages through its table row
  where they lie, and the program returns only the new positions. Every
  other stage, and every chunked-prefill step, gathers a slot group's
  windows into the dense layout the stage's decode program takes. Either
  way a scatter writes the one new position back, and another writes a
  freshly prefilled request into its pages.
* **Bit identity with the dense path** (the gathering path; the in-place
  one agrees within rounding). A gathered window agrees with the
  dense group cache at every position a live request's decode can observe:
  positions ``<= pos`` hold the identical prefill/decode writes, positions
  ``> pos`` are masked by the attention kernels (finite values, exactly
  zero weight). Unmapped pages gather as zeros — the same zero padding the
  dense prefill scatter leaves behind. Retired/parked slots carry slot id
  ``-1``: their gathers fill zeros and their scatters drop.
* **Shared-prefix pages are refcounted.** When a new request repeats a live
  request's page-aligned token prefix (equal prompt lengths, so both
  prefills are the same jitted program — same math bitwise), its table row
  points at the owner's pages, the refcount rises, and its prefill scatter
  masks those entries so the owner is never written.

The ``pages_per_req``/``page_len`` geometry requires
``page_len * pages_per_req == cache_len`` so every mapped page is fully
overwritten by the admission prefill — recycled pages never need zeroing.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Sequence, Tuple

#: cache leaves whose axis after the batch axis is the cache *position* —
#: these are paged. Everything else (``h``/``tail_x``/``tail_bc``/cross
#: ``xk``/``xv``) is whole-request state and lives in the per-slot row pool.
POSITIONAL_KEYS = frozenset({"k", "v", "c", "kpe", "pos"})


@dataclasses.dataclass(frozen=True)
class PagedCacheSpec:
    """The paged-pool geometry."""

    page_len: int
    num_pages: int
    max_requests: int                 # num_groups * group_size slot ids
    pages_per_req: int                # cache_len // page_len

    def __post_init__(self):
        for name in ("page_len", "num_pages", "max_requests",
                     "pages_per_req"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")

    @property
    def cache_len(self) -> int:
        return self.page_len * self.pages_per_req

    def pages_needed(self, need_len: int) -> int:
        """Pages covering ``need_len`` cache positions."""
        return max(1, math.ceil(need_len / self.page_len))


def map_cache_tree(tree, fn):
    """Map ``fn(key, leaf, stacked)`` over a serve cache tree
    ``{"prologue": [{k: leaf}, ...], "body": [{k: leaf}, ...]}``. ``body``
    leaves carry a leading periods axis (``stacked=True``)."""
    pro = [{k: fn(k, v, False) for k, v in blk.items()}
           for blk in tree["prologue"]]
    body = [{k: fn(k, v, True) for k, v in blk.items()}
            for blk in tree["body"]]
    return {"prologue": pro, "body": body}


def map2_cache_tree(a, b, fn):
    """Two-tree variant of :func:`map_cache_tree` (same structure)."""
    pro = [{k: fn(k, x[k], y[k], False) for k in x}
           for x, y in zip(a["prologue"], b["prologue"])]
    body = [{k: fn(k, x[k], y[k], True) for k in x}
            for x, y in zip(a["body"], b["body"])]
    return {"prologue": pro, "body": body}


def slab_bytes(template, spec: PagedCacheSpec) -> int:
    """Persistent paged-pool bytes for one stage, from the dense group-cache
    ``jax.eval_shape`` template: page slabs for positional leaves, row pools
    for state leaves, plus the page table and cursor tensors."""
    total = 0

    def add(k, leaf, stacked):
        nonlocal total
        shape = _slab_shape(k, leaf.shape, stacked, spec)
        n = 1
        for d in shape:
            n *= int(d)
        total += n * leaf.dtype.itemsize
        return None

    map_cache_tree(template, add)
    total += spec.max_requests * spec.pages_per_req * 4   # page table int32
    total += spec.max_requests * 2 * 4                    # cursors + lengths
    return total


def dense_bytes(template, num_groups: int) -> int:
    """Persistent dense-cache bytes for one stage: one group cache block per
    slot group."""
    total = 0

    def add(k, leaf, stacked):
        nonlocal total
        n = 1
        for d in leaf.shape:
            n *= int(d)
        total += n * leaf.dtype.itemsize
        return None

    map_cache_tree(template, add)
    return total * num_groups


def _slab_shape(key: str, dense_shape, stacked: bool,
                spec: PagedCacheSpec) -> Tuple[int, ...]:
    """Dense group-cache leaf shape -> slab/pool shape.

    Positional leaves: ``(B, L, *f)`` -> ``(num_pages, page_len, *f)``
    (body: leading periods axis kept). State leaves: ``(B, *f)`` ->
    ``(max_requests, *f)``."""
    if key in POSITIONAL_KEYS:
        if stacked:
            return ((dense_shape[0], spec.num_pages, spec.page_len)
                    + tuple(dense_shape[3:]))
        return (spec.num_pages, spec.page_len) + tuple(dense_shape[2:])
    if stacked:
        return (dense_shape[0], spec.max_requests) + tuple(dense_shape[2:])
    return (spec.max_requests,) + tuple(dense_shape[1:])


class PagePool:
    """Driver-side page bookkeeping: the page table, the free stack and the
    per-page refcounts. Pure numpy — the device only ever sees table *rows*
    shipped inside work items, so the pool state never needs to live in (or
    be synchronized across) the stage workers."""

    def __init__(self, spec: PagedCacheSpec):
        import numpy as np

        self.spec = spec
        self.page_table = np.full(
            (spec.max_requests, spec.pages_per_req), -1, np.int32)
        self.ref_counts = np.zeros((spec.num_pages,), np.int32)
        self.req_len = np.zeros((spec.max_requests,), np.int32)
        self._free: List[int] = list(range(spec.num_pages - 1, -1, -1))
        self.peak_pages = 0

    def free_count(self) -> int:
        return len(self._free)

    def used_pages(self) -> int:
        return self.spec.num_pages - len(self._free)

    def alloc(self, sid: int, n_own: int, shared: Sequence[int] = ()):
        """Map slot ``sid``: ``shared`` page ids first (refcounted, owned by
        another live request) then ``n_own`` fresh pages. Returns the int32
        *write row*: the full row with the shared entries masked to ``-1``,
        so the admission prefill scatter never touches the owner's pages."""
        import numpy as np

        spec = self.spec
        if not (0 <= sid < spec.max_requests):
            raise ValueError(f"slot id {sid} outside [0, {spec.max_requests})")
        if (self.page_table[sid] >= 0).any():
            raise ValueError(f"slot id {sid} is already mapped; free it first")
        n_shared = len(shared)
        if n_shared + n_own > spec.pages_per_req:
            raise ValueError(
                f"request needs {n_shared + n_own} pages but pages_per_req="
                f"{spec.pages_per_req} (cache_len / page_len)")
        if n_own > len(self._free):
            raise ValueError(
                f"page pool exhausted: need {n_own} pages, {len(self._free)} "
                f"free of {spec.num_pages}")
        row = np.full((spec.pages_per_req,), -1, np.int32)
        write_row = row.copy()
        for i, p in enumerate(shared):
            if self.ref_counts[p] < 1:
                raise ValueError(f"cannot share unreferenced page {p}")
            row[i] = p
            self.ref_counts[p] += 1
        for i in range(n_own):
            p = self._free.pop()
            row[n_shared + i] = p
            write_row[n_shared + i] = p
            self.ref_counts[p] = 1
        self.page_table[sid] = row
        self.req_len[sid] = 0
        self.peak_pages = max(self.peak_pages, self.used_pages())
        return write_row

    def free(self, sid: int) -> None:
        """Unmap slot ``sid``; pages return to the free stack when their
        refcount hits zero (shared-prefix pages outlive their allocator)."""
        for p in self.page_table[sid]:
            p = int(p)
            if p < 0:
                continue
            self.ref_counts[p] -= 1
            if self.ref_counts[p] == 0:
                self._free.append(p)
            elif self.ref_counts[p] < 0:
                raise AssertionError(f"page {p} refcount underflow")
        self.page_table[sid] = -1
        self.req_len[sid] = 0

    def row(self, sid: int):
        import numpy as np

        return np.array(self.page_table[sid], np.int32)

    def rows(self, sids: Sequence[int]):
        """Stack table rows for a slot group; ``sid < 0`` (parked) rows are
        all ``-1`` so their gathers fill zeros and their scatters drop."""
        import numpy as np

        out = np.full((len(sids), self.spec.pages_per_req), -1, np.int32)
        for i, sid in enumerate(sids):
            if sid >= 0:
                out[i] = self.page_table[sid]
        return out


class PagedStageCache:
    """One stage's paged serving state: the page slabs + row pools, and the
    jitted gather/scatter programs around the stage programs. Built lazily
    (like the dense per-group caches) in whichever worker owns the stage.

    Every scatter takes ``self.slabs`` by donation and updates it in place;
    ``self.slabs`` is then the scatter's output. Two invariants make that
    safe: one actor owns each stage's cache, so fires of one stage never
    overlap; and nothing may keep a slab leaf across a scatter (a kept
    leaf is deleted). A fire's gather or in-place decode program reads the
    slabs before its scatter is dispatched, on the same device queue, so
    the donation waits for it."""

    def __init__(self, stage, group_size: int, cache_len: int,
                 spec: PagedCacheSpec):
        if spec.cache_len != cache_len:
            raise ValueError(
                f"page_len={spec.page_len} * pages_per_req="
                f"{spec.pages_per_req} = {spec.cache_len} must equal "
                f"cache_len={cache_len}")
        self.stage = stage
        self.group_size = group_size
        self.cache_len = cache_len
        self.spec = spec
        self.slabs = None
        self._fns = None

    # -- lazy slab + program construction ---------------------------------

    def _ensure(self) -> None:
        if self.slabs is not None:
            return
        import jax
        import jax.numpy as jnp

        spec = self.spec
        tok = jnp.zeros((self.group_size,), jnp.int32)
        template = jax.eval_shape(self.stage.init_caches, tok)
        self.slabs = map_cache_tree(
            template,
            lambda k, leaf, stacked: jnp.zeros(
                _slab_shape(k, leaf.shape, stacked, spec), leaf.dtype))
        self._fns = _build_paged_ops(spec, self.group_size, self.cache_len)

    # -- the three work kinds ---------------------------------------------

    def run_decode(self, work, xin):
        """One decode fire; the scatter writes the one position each live
        slot wrote (plus the full per-request state rows) into the slabs.

        Where the stage has ``decode_slabs`` (GQA ``k``/``v`` caches on the
        TPU) its program reads the slabs in place through the table rows
        and returns only the new positions. Elsewhere (MLA latents, row-pool
        state, other platforms) the group's windows are gathered into the
        dense layout the stage's ``decode`` program takes, and the scatter
        reads the new positions out of the window it returns. Each path
        has its own scatter program (``scatter_decode_new``,
        ``scatter_decode``)."""
        import jax
        from jax.profiler import TraceAnnotation

        from repro.core.lowering import split_moe_counts

        self._ensure()
        stage = self.stage
        if stage.decode_slabs is not None:
            with TraceAnnotation("stage.program", kind="decode"):
                xout, new = split_moe_counts(stage, stage.decode_slabs(
                    stage.params, self.slabs, work.rows, work.sids, xin,
                    work.pos))
            scatter = self._fns["scatter_decode_new"]
        else:
            with TraceAnnotation("stage.gather", kind="decode"):
                window = self._fns["gather"](self.slabs, work.rows,
                                             work.sids)
            with TraceAnnotation("stage.program", kind="decode"):
                xout, new = split_moe_counts(stage, stage.decode(
                    stage.params, window, xin, work.pos))
            scatter = self._fns["scatter_decode"]
        with TraceAnnotation("stage.sync", kind="decode"):
            xout = jax.block_until_ready(xout)
        with TraceAnnotation("stage.scatter", kind="decode"):
            self.slabs = scatter(self.slabs, work.rows, work.sids, work.pos,
                                 new)
        return xout

    def write_prefill(self, work, slot_caches) -> None:
        """Scatter a freshly prefilled request into its mapped pages.
        ``work.row`` is the *write* row — shared-prefix entries are ``-1``
        so the prefix owner's pages are read-only."""
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        self._ensure()
        with TraceAnnotation("stage.scatter", kind="prefill"):
            self.slabs = self._fns["scatter_prefill"](
                self.slabs, jnp.asarray(work.row), jnp.int32(work.sid),
                slot_caches)

    def run_chunk(self, work, xin):
        """One chunked-prefill step: gather (state rows read via
        ``sids_in``, ``-1`` on the first chunk so recurrent state starts
        from exact zeros), run the stage's scan-of-decode chunk program,
        scatter the chunk's positions and the final state row back."""
        import jax
        from jax.profiler import TraceAnnotation

        from repro.core.lowering import split_moe_counts

        self._ensure()
        with TraceAnnotation("stage.gather", kind="chunk"):
            window = self._fns["gather"](self.slabs, work.rows, work.sids_in)
        with TraceAnnotation("stage.program", kind="chunk"):
            xout, new_window = split_moe_counts(
                self.stage, self.stage.chunk(self.stage.params, window, xin,
                                             work.pos0, work.adv))
        with TraceAnnotation("stage.sync", kind="chunk"):
            xout = jax.block_until_ready(xout)
        with TraceAnnotation("stage.scatter", kind="chunk"):
            self.slabs = self._fns["scatter_chunk"](int(work.toks.shape[0]))(
                self.slabs, work.rows, work.sids_out, work.pos0, work.adv,
                new_window)
        return xout


def _build_paged_ops(spec: PagedCacheSpec, group_size: int, cache_len: int):
    """Jit the fixed-shape gather/scatter programs for one stage.

    The scatters donate their ``slabs`` argument, so they write their few
    positions into the slabs in place instead of copying each whole slab
    first. The caller owns the slabs alone (one actor per stage, no
    overlapping fires), replaces them with each scatter's output, and keeps
    no slab leaf across a scatter.

    Physical index math: cache position ``pos`` of the slot with table row
    ``row`` lives at flat slab index ``row[pos // page_len] * page_len +
    pos % page_len``. Unmapped pages (entry ``-1``) and parked slots
    (``sid < 0``) are redirected to the out-of-bounds sentinel
    ``num_pages * page_len`` — gathers fill 0 there, scatters drop; the
    decode scatter, which writes each slot in place, writes back instead
    the value it reads there."""
    import jax
    import jax.numpy as jnp

    B, L, pl = group_size, cache_len, spec.page_len
    total = spec.num_pages * pl
    mr = spec.max_requests

    def _flat(slab, stacked):
        if stacked:
            return slab.reshape((slab.shape[0], total) + slab.shape[3:])
        return slab.reshape((total,) + slab.shape[2:])

    def gather(slabs, rows, sids):
        rows = jnp.asarray(rows, jnp.int32)
        sids = jnp.asarray(sids, jnp.int32)
        pos = jnp.arange(L)
        page = rows[:, pos // pl]                        # (B, L)
        phys = jnp.where(page >= 0, page * pl + pos[None, :] % pl, total)
        sid_idx = jnp.where(sids >= 0, sids, mr)         # OOB -> fill 0

        def g(k, slab, stacked):
            if k in POSITIONAL_KEYS:
                return jnp.take(_flat(slab, stacked), phys,
                                axis=1 if stacked else 0,
                                mode="fill", fill_value=0)
            return jnp.take(slab, sid_idx, axis=1 if stacked else 0,
                            mode="fill", fill_value=0)
        return map_cache_tree(slabs, g)

    def make_scatter_decode(value_at):
        """The decode scatter, writing each live slot's one new position
        in place; ``value_at(new, i, lead, pos_i, feat, size)`` is slot
        ``i``'s entry of a positional leaf of the decode program's output
        ``new``, shaped like a one-position slice of the slab."""
        def scatter_decode(slabs, rows, sids, pos, new):
            rows = jnp.asarray(rows, jnp.int32)
            sids = jnp.asarray(sids, jnp.int32)
            pos = jnp.asarray(pos, jnp.int32)
            b = jnp.arange(B)
            page = rows[b, pos // pl]                        # (B,)
            ok = (page >= 0) & (sids >= 0)
            page = jnp.where(ok, page, 0)
            off = pos % pl
            sid_idx = jnp.where(sids >= 0, sids, mr)         # OOB -> drop
            zero = jnp.zeros((), jnp.int32)

            def sc(k, slab, win, stacked):
                if k in POSITIONAL_KEYS:
                    # each slot's position written in place on the slab
                    # itself: a scatter into a flattened view lets the
                    # compiler relayout (copy) a slab whose feature width is
                    # no multiple of 128; parked and unmapped slots write
                    # back what is there
                    lead = (zero,) if stacked else ()
                    feat = (zero,) * (slab.ndim - len(lead) - 2)
                    size = slab.shape[:len(lead)] + (1, 1) + slab.shape[
                        len(lead) + 2:]
                    for i in range(B):
                        start = lead + (page[i], off[i]) + feat
                        cur = jax.lax.dynamic_slice(slab, start, size)
                        val = value_at(win, i, lead, pos[i], feat, size)
                        slab = jax.lax.dynamic_update_slice(
                            slab, jnp.where(ok[i], val.astype(slab.dtype),
                                            cur), start)
                    return slab
                if stacked:
                    return slab.at[:, sid_idx].set(win.astype(slab.dtype),
                                                   mode="drop")
                return slab.at[sid_idx].set(win.astype(slab.dtype),
                                            mode="drop")
            return map2_cache_tree(slabs, new, sc)
        return scatter_decode

    # ``new`` is the whole window the dense decode program returns ...
    scatter_decode = make_scatter_decode(
        lambda win, i, lead, p, feat, size: jax.lax.dynamic_slice(
            win, lead + (jnp.int32(i), p) + feat, size))
    # ... or (``decode_slabs``) only the new positions, one axis fewer
    scatter_decode_new = make_scatter_decode(
        lambda new, i, lead, p, feat, size: jax.lax.expand_dims(
            jax.lax.index_in_dim(new, i, len(lead)), (len(lead) + 1,)))
    scatter_decode_new.__name__ = "scatter_decode_new"

    def scatter_prefill(slabs, write_row, sid, slot_caches):
        write_row = jnp.asarray(write_row, jnp.int32)
        pos = jnp.arange(L)
        page = write_row[pos // pl]
        phys = jnp.where(page >= 0, page * pl + pos % pl, total)
        sid_idx = jnp.where(sid >= 0, sid, mr)

        def sc(k, slab, sc_leaf, stacked):
            if k in POSITIONAL_KEYS:
                flat = _flat(slab, stacked)
                if stacked:
                    flat = flat.at[:, phys].set(
                        sc_leaf[:, 0].astype(slab.dtype), mode="drop")
                else:
                    flat = flat.at[phys].set(sc_leaf[0].astype(slab.dtype),
                                             mode="drop")
                return flat.reshape(slab.shape)
            if stacked:
                return slab.at[:, sid_idx].set(
                    sc_leaf[:, 0].astype(slab.dtype), mode="drop")
            return slab.at[sid_idx].set(sc_leaf[0].astype(slab.dtype),
                                        mode="drop")
        return map2_cache_tree(slabs, slot_caches, sc)

    def make_scatter_chunk(T: int):
        b = jnp.arange(B)

        def scatter_chunk_T(slabs, rows, sids, pos0, adv, window):
            rows = jnp.asarray(rows, jnp.int32)
            sids = jnp.asarray(sids, jnp.int32)
            pos0 = jnp.asarray(pos0, jnp.int32)
            adv = jnp.asarray(adv, jnp.int32)
            pos_m = pos0[:, None] + jnp.arange(T)[None, :] * adv[:, None]
            page = jnp.take_along_axis(rows, pos_m // pl, axis=1)  # (B, T)
            ok = (page >= 0) & (sids >= 0)[:, None]
            phys = jnp.where(ok, page * pl + pos_m % pl, total)
            sid_idx = jnp.where(sids >= 0, sids, mr)

            def sc(k, slab, win, stacked):
                if k in POSITIONAL_KEYS:
                    flat = _flat(slab, stacked)
                    if stacked:
                        val = win[:, b[:, None], pos_m]  # (P, B, T, *f)
                        flat = flat.at[:, phys].set(val.astype(slab.dtype),
                                                    mode="drop")
                    else:
                        val = win[b[:, None], pos_m]     # (B, T, *f)
                        flat = flat.at[phys].set(val.astype(slab.dtype),
                                                 mode="drop")
                    return flat.reshape(slab.shape)
                if stacked:
                    return slab.at[:, sid_idx].set(win.astype(slab.dtype),
                                                   mode="drop")
                return slab.at[sid_idx].set(win.astype(slab.dtype),
                                            mode="drop")
            return map2_cache_tree(slabs, window, sc)
        return jax.jit(scatter_chunk_T, donate_argnums=(0,))

    chunk_fns: Dict[int, Any] = {}

    def scatter_chunk(T):
        # one jit specialization per chunk length (mirrors the per-length
        # prefill specializations of the dense path), slabs donated
        if T not in chunk_fns:
            chunk_fns[T] = make_scatter_chunk(T)
        return chunk_fns[T]

    return {"gather": jax.jit(gather),
            "scatter_decode": jax.jit(scatter_decode, donate_argnums=(0,)),
            "scatter_decode_new": jax.jit(scatter_decode_new,
                                          donate_argnums=(0,)),
            "scatter_prefill": jax.jit(scatter_prefill, donate_argnums=(0,)),
            "scatter_chunk": scatter_chunk}
