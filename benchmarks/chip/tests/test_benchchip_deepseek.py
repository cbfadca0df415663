"""DeepSeek-V2-Lite's configuration files: the work counts against counts
made by hand from the published widths, and, at widths cut to a few dozen
on the CPU, a run of its cell and the served logits against its plain
reference."""
import time

import jax
import numpy as np
import pytest

import benchchip_tiny as tiny
from benchchip import registry
from benchchip.peaks import PEAKS

SEED = 2**33 + 11

# deepseek-v2-lite: 27 layers (layer 0 dense), d 2048, 16 heads, MLA with
# latent 512, nope 128, rope 64, v 128; dense ff 10944; 26 expert layers of
# 64 routed experts (16 held here) of ff 1408, 6 a token, 2 shared; vocab
# 102400
ATTN = (2048 * 16 * 192            # wq
        + 2048 * (512 + 64)        # wkv_a
        + 512 * 16 * 128           # w_uk
        + 512 * 16 * 128           # w_uv
        + 16 * 128 * 2048)         # wo
EXPERT = 3 * 2048 * 1408
DENSE = 3 * 2048 * 10944
ROUTER = 2048 * 64
SHARED = 2 * EXPERT
HEAD = 2048 * 102400
LATENT_TOKEN = 27 * (512 + 64) * 2   # c and k_pe, every layer, bf16


@pytest.fixture(scope="module")
def deepseek():
    return registry.config_spec("deepseek-v2-lite"), registry.config_module(
        "deepseek-v2-lite")


def test_deepseek_counts(deepseek):
    spec, m = deepseek
    assert ATTN == 13_762_560 and EXPERT == 8_650_752
    assert m.expert_params(spec) == EXPERT
    # a token's held experts: 6 of 64 routed, 16 held -> 1.5 on average
    assert m.matmul_params(spec) == (27 * ATTN + DENSE + HEAD
                                     + 26 * (ROUTER + SHARED
                                             + 3 * EXPERT // 2))
    assert m.latent_bytes_per_token(spec) == LATENT_TOKEN == 31_104
    # q.k over nope + rope, p.v over v, 16 heads, every layer
    assert m.token_flops(spec, 1000) == (2 * m.matmul_params(spec)
                                         + 27 * 2 * 16 * (192 + 128) * 1000)
    wbytes = 2 * (27 * ATTN + DENSE + 26 * (ROUTER + SHARED) + HEAD
                  + 27 * (2 * 2048 + 512) + 2048)
    assert m.weight_bytes(spec) == wbytes
    assert m.touched_experts(spec, 1) == pytest.approx(16 * 6 / 64)
    assert m.touched_experts(spec, 8) == pytest.approx(
        16 * (1 - (58 / 64) ** 8))          # about 8.7 of 16
    flops, nbytes = m.decode_work(spec, [100, 200])
    assert flops == (2 * m.token_flops(spec, 0)
                     + 27 * 2 * 16 * 320 * (100 + 200))
    assert nbytes == pytest.approx(
        wbytes + 26 * 16 * (1 - (58 / 64) ** 2) * EXPERT * 2
        + (100 + 200) * LATENT_TOKEN + 2 * LATENT_TOKEN
        + 2 * (2 * 2048 + 4 * 102400))
    # causal prefill of 768: projections of every token, the head once,
    # q.k and p.v over the lower triangle (768 * 769 / 2 pairs)
    assert m.prefill_flops(spec, 768) == (
        2 * (m.matmul_params(spec) - HEAD) * 768 + 2 * HEAD
        + 27 * 2 * 16 * 320 * (768 * 769 // 2))


def test_decode_need_is_memory_bound(deepseek):
    spec, m = deepseek
    p = PEAKS["TPU v5 lite"]
    flops, nbytes = m.decode_work(spec, [1536] * 8)
    assert nbytes / p["hbm_bytes_per_s"] > flops / p["bf16_flops"]


def tiny_files(**serve):
    """``CellFiles`` of a tiny DeepSeek backlog cell: every width cut to a
    few dozen, 16 routed experts of which 4 are held, 3 a token."""
    R = tiny.run_module()
    spec = registry.config_spec("deepseek-v2-lite")
    spec.update(num_hidden_layers=3, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_attention_heads=4,
                num_key_value_heads=4, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                n_routed_experts=4, num_experts_per_tok=3, vocab_size=4096)
    spec["deployment"] = dict(spec["deployment"],
                              published_n_routed_experts=16)
    mod = registry.config_module("deepseek-v2-lite")
    mix = {"loop": "backlog", "rate_per_s": 4.0,
           "prompt": {"dist": "uniform", "min": 16, "max": 32,
                      "multiple": 16},
           "output": {"dist": "uniform", "min": 16, "max": 24}}
    workload = {"name": "tiny-deepseek", "config": "deepseek",
                "traffic": "deepseek", "chips": 1}
    cell = {"serve": {"num_groups": 2, "group_size": 2, "cache_len": 64,
                      **serve},
            "check": {"tokens": 160, "max_requests": 16,
                      "limits": {"mean_logit_gap": TINY_LIMIT}}}
    return R.CellFiles(workload, spec, mod, mix, cell)


#: the tiny cell's limit on the mean logit gap. At these widths, over the
#: 80 tokens a one-second window checks, on eight seeds, the bfloat16
#: program read at most 0.000513; the float8 control read at least 0.00185
TINY_LIMIT = 0.0009


def test_tiny_cell_runs_and_is_correct():
    R = tiny.run_module()
    names = ("setup_s", "serve_tok_s", "tokens_per_round", "round_ms",
             "step_mfu", "expert_pad_share", "expert_unused_read_share")
    result, checks = R.measure(
        tiny_files(), [{"name": n, "unit": "x"} for n in names], seed=SEED,
        seconds=1.0, trace=False, devices=jax.devices(),
        peaks=PEAKS["TPU v5 lite"], t_start=time.perf_counter())
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == set(names)
    # every held expert runs over every token: 3 of 16 experts a token, 4
    # held, so about 3/16 of the rows carry a pair; every held expert is
    # read, some of them untouched
    pad = result["metrics"]["expert_pad_share"]["value"]
    assert 60.0 < pad < 100.0
    unused = result["metrics"]["expert_unused_read_share"]["value"]
    assert 0.0 <= unused < 100.0
    assert checks["mean_logit_gap"][0] <= TINY_LIMIT


def test_served_logits_match_the_reference():
    """Prefill and then paged decode through two actor stages agree with
    the plain reference's full forward pass over the served sequence, in
    logits at every served position. The program computes in float32 here;
    its latent cache holds bfloat16, whose rounding (2^-8 of each entry)
    bounds the decode steps' difference."""
    from repro import api
    from repro.configs.base import ModelConfig

    files = tiny_files()
    spec = dict(files.spec, torch_dtype="float32")
    mod = files.module
    cfg = ModelConfig(**mod.model_kwargs(spec))
    weights = jax.jit(lambda k: mod.init_weights(spec, k))(
        jax.random.PRNGKey(5))
    seen = []

    def record(stage_index, fn):
        if stage_index != 1:
            return fn

        def wrapped(payload):
            out = fn(payload)
            seen.append((out["work"], np.asarray(out["result"])))
            return out
        return wrapped

    sess = api.compile(cfg, mode="serve", backend="actors", stages=2,
                       cache="paged", params=weights, num_groups=1,
                       group_size=2, max_prompt_len=24, max_new_tokens=8,
                       cache_len=48, page_len=8, fn_wrap=record)
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, cfg.vocab_size, n).astype(np.int32), g)
            for n, g in ((24, 8), (17, 6))]
    outs = sess.generate(reqs)
    sess.close()

    # each request's logits in order: its prefill, then its decode rows
    slot_of, logits = {}, [[] for _ in reqs]
    for work, res in seen:
        if type(work).__name__ == "PrefillWork":
            i = len(slot_of)
            slot_of[work.slot] = i
            logits[i].append(res[0])
        else:
            for slot, i in slot_of.items():
                if len(logits[i]) < reqs[i][1]:
                    logits[i].append(res[slot])
    S = max(len(p) + g for p, g in reqs)
    toks = np.zeros((len(reqs), S), np.int32)
    at = np.zeros((len(reqs), max(g for _, g in reqs)), np.int32)
    for b, ((p, g), o) in enumerate(zip(reqs, outs)):
        assert len(o) == g and len(logits[b]) == g
        seq = np.concatenate([p, o])
        toks[b, :seq.size] = seq
        at[b, :g] = len(p) - 1 + np.arange(g)
    want = mod.reference_logits(spec, weights, toks, at)
    for b, (_, g) in enumerate(reqs):
        got = np.stack(logits[b])[:, :spec["vocab_size"]]
        # the cache's rounding reads 8e-4 of the largest logit here; a
        # program without YaRN's frequencies or its softmax factor reads
        # 0.15 and 0.13
        scale = np.abs(want[b, :g]).max()
        np.testing.assert_allclose(got, want[b, :g], rtol=0,
                                   atol=3e-3 * scale)
        # greedy tokens follow the served logits
        assert np.array_equal(np.argmax(got, -1), outs[b])

