"""One serving cell: build the session from the seed, warm up every shape
the mix can draw, drive the measured window through ``ServeSession.generate``
and keep what the metrics and the correctness check read.

The window drives the public serving entry. ``generate`` returns when every
request of the call has finished, so a request is answered when the call
that took it returns:

each call takes every request that is due when it starts; with none due the
load generator sleeps until the next due time. The requests of a window of
``seconds`` (:meth:`benchchip.traffic.Traffic.schedule`) are sent, and the
window ends when the last has been answered, so it is a whole number of
calls. A backlog, all due when the window opens, is one call.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchchip.traffic import Request, Traffic


def weight_seed(seed: int) -> int:
    """A 31-bit weight key derived from any whole-number seed."""
    return int(np.random.default_rng([seed, 1]).integers(0, 2 ** 31))


def make_weights(module, spec: dict, seed: int):
    """The configuration's weights in one jitted call on the device."""
    import jax

    key = jax.random.PRNGKey(weight_seed(seed))
    w = jax.jit(functools.partial(module.init_weights, spec))(key)
    return jax.block_until_ready(w)


@dataclasses.dataclass
class Served:
    req: Request
    start: float          # the answering call's start, seconds into the window
    end: float            # its return
    tokens: np.ndarray


@dataclasses.dataclass
class Window:
    span: float = 0.0                  # first call's start to last call's end
    calls: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    served: List[Served] = dataclasses.field(default_factory=list)
    attempted: int = 0
    lowerings: int = 0                 # programs traced inside the window


class DecodeRecorder:
    """``fn_wrap`` for the first stage: keeps every work item's kind and
    its device arrays by reference (no host sync on the hot path), read
    once the window has closed. ``park`` is the position a retired slot
    decodes at (the session's ``cache_len - 1``)."""

    def __init__(self):
        self.items: List[tuple] = []
        self.call = -1
        self.park = -1

    def __call__(self, stage_index: int, fn):
        if stage_index != 0:
            return fn

        def wrapped(payload):
            self.items.append((self.call, payload["work"]))
            return fn(payload)
        return wrapped

    def decode_ctxs(self, calls=None) -> List[List[int]]:
        """Live context lengths (``pos + 1`` of each live slot) of every
        decode item, optionally only those of ``calls``. A paged item names
        its live slots (``sids >= 0``); a dense one parks the others."""
        out = []
        for call, w in self.items:
            if calls is not None and call not in calls:
                continue
            if type(w).__name__ != "DecodeWork":
                continue
            pos = np.asarray(w.pos)
            live = (np.asarray(w.sids) >= 0 if w.sids is not None
                    else pos != self.park)
            out.append([int(p) + 1 for p in pos[live]])
        return out


class CompileCounter:
    """Counts programs lowered while ``active`` (none should be, inside
    the window)."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_a, **_k):
        if self.active and name.endswith("jaxpr_to_mlir_module_duration"):
            self.count += 1


#: ``api.compile`` serving options a cell's ``serve`` object leaves out
SERVE_DEFAULTS = {"backend": "actors", "cache": "paged"}


def serve_options(serve: dict) -> dict:
    """``api.compile(mode="serve")`` keyword arguments from a cell's
    ``serve`` object: every key passes through (``num_groups``,
    ``group_size``, ``cache_len``, ``cache``, ``prefill_chunk``, ...) over
    :data:`SERVE_DEFAULTS`, and ``mesh``, the ``[data, model]`` axis sizes,
    becomes a device mesh."""
    opts = dict(SERVE_DEFAULTS, **serve)
    if "mesh" in opts:
        from repro.launch.mesh import make_mesh

        opts["mesh"] = make_mesh(tuple(opts["mesh"]), ("data", "model"))
    return opts


class ServeCell:
    """A cell's session, built from the seed."""

    def __init__(self, cell: dict, spec: dict, module, mix: dict, seed: int,
                 *, record: bool = False):
        from repro import api
        from repro.configs.base import ModelConfig

        self.cell, self.spec, self.module, self.seed = cell, spec, module, seed
        opts = serve_options(cell["serve"])
        self.slots = opts["num_groups"] * opts["group_size"]
        self.cfg = ModelConfig(**module.model_kwargs(spec))
        self.traffic = Traffic(dict(mix, **cell.get("load", {})),
                               vocab=self.cfg.vocab_size, seed=seed,
                               slots=self.slots)
        self.recorder = DecodeRecorder() if record else None
        weights = make_weights(module, spec, seed)
        self.sess = api.compile(
            self.cfg, mode="serve", params=weights,
            max_prompt_len=self.traffic.max_prompt(),
            max_new_tokens=self.traffic.max_gen(),
            fn_wrap=self.recorder, **opts)
        del weights
        if record:
            self.recorder.park = self.sess.cache_len - 1

    def warm_up(self) -> None:
        """One call holding a request of every prompt length the mix can
        draw: every prefill program, the decode, gather and scatter
        programs, at the cell's shapes. Its prompts are not the window's."""
        rng = np.random.default_rng([self.seed, 3])
        reqs = [(rng.integers(0, self.cfg.vocab_size, n).astype(np.int32), 2)
                for n in self.traffic.prompt_buckets()]
        self.sess.generate(reqs)

    def close(self) -> None:
        self.sess.close()
        self.sess = None
        gc.collect()

    # -- the window -------------------------------------------------------

    def _call(self, win: Window, reqs: List[Request], t0: float,
              annotate) -> None:
        hist0 = len(self.sess.history)
        if self.recorder is not None:
            self.recorder.call = len(win.calls)
        start = time.perf_counter() - t0
        with annotate("bench.generate"):
            outs = self.sess.generate([(r.prompt, r.gen) for r in reqs])
        end = time.perf_counter() - t0
        rounds = [h["makespan"] for h in self.sess.history[hist0:]
                  if h["kind"] == "round"]
        win.calls.append({"start": start, "end": end, "requests": len(reqs),
                          "stats": dict(self.sess.last_stats),
                          "makespans": rounds})
        for r, o in zip(reqs, outs):
            win.served.append(Served(r, start, end, np.asarray(o)))

    def run_window(self, seconds: float, counter: CompileCounter,
                   annotate) -> Window:
        win = Window()
        sched = self.traffic.schedule(seconds)
        win.attempted = len(sched)
        counter.active = True
        t0 = time.perf_counter()
        i = 0
        while i < len(sched):
            now = time.perf_counter() - t0
            if sched[i].due > now:
                with annotate("bench.wait"):
                    time.sleep(sched[i].due - now)
                continue
            j = i
            while j < len(sched) and sched[j].due <= now:
                j += 1
            self._call(win, sched[i:j], t0, annotate)
            i = j
        counter.active = False
        win.lowerings = counter.count
        win.span = win.calls[-1]["end"] - win.calls[0]["start"]
        return win


# ---------------------------------------------------------------------------
# correctness: served tokens against the plain reference
# ---------------------------------------------------------------------------

def sample(win: Window, seed: int, tokens: int, max_requests: int):
    """Requests to check, drawn from the seed: the one that served the
    most tokens, then others until ``tokens`` served tokens are covered."""
    done = sorted(win.served, key=lambda s: (-len(s.tokens),
                                             -s.req.prompt.size, s.req.rid))
    if not done:
        return []
    rng = np.random.default_rng([seed, 2])
    rest = [done[i] for i in rng.permutation(np.arange(1, len(done)))]
    out = [done[0]]
    for s in rest:
        if (sum(len(x.tokens) for x in out) >= tokens
                or len(out) >= max_requests):
            break
        out.append(s)
    return out


def reference_gaps(module, spec: dict, weights, picked: List[Served],
                   lowp: Optional[str] = None, block: Optional[int] = None):
    """For every served token of ``picked``: how far the float32
    reference's logit of that token lies below the reference's best
    logit at its position. With ``lowp`` the tokens are instead the ones
    a float8 run of the reference puts first at each position (the
    control). The reference runs over ``block`` requests at a time (all
    at once by default)."""
    S = max(s.req.prompt.size + len(s.tokens) for s in picked)
    S = -(-S // 128) * 128
    K = max(len(s.tokens) for s in picked)
    toks = np.zeros((len(picked), S), np.int32)
    at = np.zeros((len(picked), K), np.int32)
    for b, s in enumerate(picked):
        p = s.req.prompt.size
        seq = np.concatenate([s.req.prompt, s.tokens.astype(np.int32)])
        toks[b, :seq.size] = seq
        at[b] = np.minimum(p - 1 + np.arange(K), p - 2 + len(s.tokens))
    ref = module.reference_logits(spec, weights, toks, at, block=block)
    if lowp is not None:
        low = module.reference_logits(spec, weights, toks, at, lowp=lowp,
                                      block=block)
    gaps = []
    for b, s in enumerate(picked):
        n = len(s.tokens)
        r = ref[b, :n]
        chosen = (np.argmax(low[b, :n], -1) if lowp is not None
                  else s.tokens)
        gaps.append(r.max(-1) - np.take_along_axis(r, chosen[:, None],
                                                   -1)[:, 0])
    return np.concatenate(gaps)


def gap_stats(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers a cell's check may compare (``check.limits`` in its
    ``cells/`` file names them): the mean gap over every checked token.
    No checked token reads as infinitely wrong."""
    return {"mean_logit_gap": float(gaps.mean()) if gaps.size
            else float("inf")}


def failed_requests(win: Window, vocab: int) -> int:
    """Requests due in the window that were not answered, or were answered
    with the wrong number of tokens or an id outside the vocabulary."""
    bad = win.attempted - len(win.served)
    for s in win.served:
        t = s.tokens
        if len(t) != s.req.gen or (t < 0).any() or (t >= vocab).any():
            bad += 1
    return bad
