"""The comparison that decides ``correct`` fails what it must: the
float8 control in the program's place, and a run whose timed path is
broken underneath (decode state left unchanged, tokens altered where they
are produced). Tiny widths on the CPU; the chip readings the limits were
set from are in PERF.md."""
import jax
import numpy as np
import pytest

import benchchip_tiny as tiny
from benchchip import serve

SEEDS = [2**33 + 1, 2**33 + 2, 2**33 + 3]


@pytest.mark.parametrize("kind", ["qwen", "mamba"])
def test_float8_control_fails_the_limit(kind):
    """The reference computed with float8 matmul inputs, put in the
    program's place over the same prompts and tokens, reads a mean gap
    above the limit on every seed."""
    files = tiny.tiny_files(kind)
    for seed in SEEDS:
        cell = serve.ServeCell(files.cell, files.spec, files.module,
                               files.mix, seed)
        cell.warm_up()
        win = cell.run_window(1.0, serve.CompileCounter(),
                              jax.profiler.TraceAnnotation)
        cell.close()
        chk = files.cell["check"]
        picked = serve.sample(win, seed, chk["tokens"], chk["max_requests"])
        w = serve.make_weights(files.module, files.spec, seed)
        prog = serve.reference_gaps(files.module, files.spec, w, picked)
        ctrl = serve.reference_gaps(files.module, files.spec, w, picked,
                                    lowp="float8_e4m3fn")
        assert serve.gap_stats(prog)["mean_logit_gap"] <= tiny.TINY_LIMIT
        assert serve.gap_stats(ctrl)["mean_logit_gap"] > tiny.TINY_LIMIT


def _stale_decode(self, work, xin):
    """A decode that returns its cache unchanged: the window is read, the
    step runs, the new entries are never written back."""
    self._ensure()
    window = self._fns["gather"](self.slabs, work.rows, work.sids)
    xout, _ = self.stage.decode(self.stage.params, window, xin, work.pos)
    return jax.block_until_ready(xout)


def _altered_tokens(orig):
    def pick(self, m, res):
        toks = orig(self, m, res)
        if toks is None or m[0] != "decode":
            return toks
        return (np.asarray(toks) + 1) % self.cfg.vocab_size
    return pick


@pytest.mark.parametrize("kind", ["qwen", "mamba"])
@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(kind, fault, monkeypatch):
    from repro import api
    from repro.serve.paged_cache import PagedStageCache

    if fault == "state_unchanged":
        monkeypatch.setattr(PagedStageCache, "run_decode", _stale_decode)
    else:
        monkeypatch.setattr(api.ServeSession, "_pick_tokens",
                            _altered_tokens(api.ServeSession._pick_tokens))
    result, checks = tiny.measure(kind, SEEDS[0])
    assert result["correct"] is False
    assert checks["mean_logit_gap"][0] > tiny.TINY_LIMIT
