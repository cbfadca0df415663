"""The expert layer that holds a share of the router's experts, and
DeepSeek-V2's YaRN latent attention, on the CPU at small widths.

Every comparison is in float32 against a plain reference written here: the
layer's math is the reference's, summed in another order, so the
tolerances are a few float32 roundings of the outputs' size.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import ModelConfig
from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
from repro.models import mlp
from repro.models.attention import mla_softmax_scale
from repro.models.common import MeshPlan, yarn_inv_freq, yarn_mscale
from repro.models.model_zoo import build_model
from repro.train.steps import plan_from_mesh

PLAN = MeshPlan.single_device()
E, K, SHARES = 64, 6, 4
T = 24
#: float32 sums of a few dozen products of unit-scale terms, in another
#: order than the reference's
TOL = dict(rtol=1e-5, atol=1e-5)


def _cfg(**over):
    kw = dict(name="moe-test", family="moe", num_layers=2, d_model=32,
              num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=256,
              num_experts=E, top_k=K, num_shared_experts=2, moe_d_ff=16,
              norm_topk_prob=False, dtype="float32")
    kw.update(over)
    return ModelConfig(**kw)


def _share(p, lo, n):
    return dict(p, **{k: p[k][lo:lo + n]
                      for k in ("w_gate", "w_up", "w_down")})


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def reference_layer(p, t, cfg, experts=range(E)):
    """The uncut layer written out: softmax over every expert, the top
    ``K``, the gates scaled (and renormalised where configured), then the
    routed experts in ``experts`` and the shared MLP. Also the pairs routed
    to ``experts`` and how many of them were touched."""
    hi = jax.lax.Precision.HIGHEST
    probs = jax.nn.softmax(jnp.dot(t, p["router"], precision=hi), -1)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    top = top * cfg.routed_scaling_factor
    s = p["shared"]
    out = _swiglu(t, s["w_gate"], s["w_up"], s["w_down"])
    pairs, touched = 0, 0
    for e in experts:
        gate = jnp.sum(jnp.where(idx == e, top, 0.0), -1)
        out = out + gate[:, None] * _swiglu(
            t, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        n = int(jnp.sum(idx == e))
        pairs += n
        touched += n > 0
    return out, pairs, touched


@pytest.fixture(scope="module")
def layer():
    cfg = _cfg()
    p = mlp.init_moe(jax.random.PRNGKey(0), cfg)
    t = jax.random.normal(jax.random.PRNGKey(1), (T, cfg.d_model))
    return cfg, p, t


def test_shares_sum_to_the_uncut_layer(layer):
    """Four chips' shares of 16 of the 64 experts, with the shared experts
    that every chip computes alike counted once, add up to the uncut
    reference layer; the aux loss is the router's, whatever the share."""
    cfg, p, t = layer
    n = E // SHARES
    outs, auxes = [], []
    for i in range(SHARES):
        c = dataclasses.replace(cfg, experts_held=n, first_expert=i * n)
        out, aux, _ = mlp.moe_forward(_share(p, i * n, n), t[None], c, PLAN)
        outs.append(out[0])
        auxes.append(float(aux))
    s = p["shared"]
    shared = _swiglu(t, s["w_gate"], s["w_up"], s["w_down"])
    want, _, _ = reference_layer(p, t, cfg)
    np.testing.assert_allclose(sum(outs) - (SHARES - 1) * shared, want,
                               **TOL)
    whole, aux, _ = mlp.moe_forward(p, t[None], cfg, PLAN)
    np.testing.assert_allclose(whole[0], want, **TOL)
    assert auxes == pytest.approx([float(aux)] * SHARES, rel=1e-6)


@pytest.mark.parametrize("held", [E, 16])
def test_one_hot_expert_takes_every_token(layer, held):
    """Every token routed first to expert 5: no capacity, so none of the
    batch's tokens is dropped, and the layer matches the reference."""
    cfg, p, _ = layer
    cfg = dataclasses.replace(cfg, experts_held=held, norm_topk_prob=True)
    t = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (T, cfg.d_model)))
    router = p["router"].at[:, 5].set(10.0)
    p = _share(dict(p, router=router), 0, held)
    _, _, idx = mlp.route(router, t, cfg)
    assert (np.asarray(idx)[:, 0] == 5).all()
    out, _, counts = mlp.moe_forward(p, t[None], cfg, PLAN)
    want, pairs, _ = reference_layer(p, t, cfg, range(held))
    np.testing.assert_allclose(out[0], want, **TOL)
    assert int(counts[0]) == pairs >= T


@pytest.mark.parametrize("form", ["zero_gate", "grouped"])
@pytest.mark.parametrize("share", [0, 3])
def test_counters_equal_the_reference_router(layer, share, form,
                                             monkeypatch):
    """The held experts' part is the reference's, in either form, and the
    layer's counts are the reference router's: pairs routed to held
    experts and held experts touched; the zero-gate form runs every held
    expert over every token (``held * T`` rows) and reads every held
    expert, the grouped form lays out the ``T * K`` pairs and reads the
    touched experts."""
    cfg, p, t = layer
    n = E // SHARES
    lo = share * n
    cfg = dataclasses.replace(cfg, experts_held=n, first_expert=lo)
    ps = _share(p, lo, n)
    _, gates, idx = mlp.route(ps["router"], t, cfg)
    e = np.asarray(idx).reshape(-1) - lo
    held = (e >= 0) & (e < n)
    fn = {"zero_gate": mlp._zero_gate_form, "grouped": mlp._grouped_form}
    out = fn[form](ps, t, jnp.asarray(np.where(held, e, n)),
                   jnp.where(jnp.asarray(held), gates.reshape(-1), 0.0))
    want, pairs, touched = reference_layer(p, t, cfg, range(lo, lo + n))
    s = p["shared"]
    routed = want - _swiglu(t, s["w_gate"], s["w_up"], s["w_down"])
    np.testing.assert_allclose(out, routed, **TOL)
    assert pairs > 0 and int(held.sum()) == pairs
    monkeypatch.setattr(mlp, "expert_form", lambda *a: form)
    got, _, counts = mlp.moe_forward(ps, t[None], cfg, PLAN)
    np.testing.assert_allclose(got[0], want, **TOL)
    rows, read = (n * T, n) if form == "zero_gate" else (T * K, touched)
    assert [int(c) for c in counts] == [pairs, rows, touched, read]


def test_expert_form_by_shape_and_platform():
    """The grouped matmul runs only on the TPU, over a large batch where the
    zero-gate form would compute many more rows: training and long prefill
    batches of jamba (16 experts, 2 a token), DeepSeek-V3 (256, 8),
    DeepSeek-V2-Lite with all 64 held (6 a token), and the 16 of 64 V2-Lite
    holds in the benchmark cell from 4096 tokens on; never the cell's
    prompts (at most 1536 tokens), a decode batch, nor off the TPU."""
    form = mlp.expert_form
    for T in (8, 256, 1536, 2048):
        assert form(16, T, 6, "tpu") == "zero_gate"       # V2-Lite's share
    for T in (4096, 8192):
        assert form(16, T, 6, "tpu") == "grouped"
    assert form(16, 2048, 2, "tpu") == "grouped"          # jamba
    assert form(256, 4096, 8, "tpu") == "grouped"         # DeepSeek-V3
    assert form(64, 512, 6, "tpu") == "zero_gate"         # V2-Lite, all held
    assert form(64, 2048, 6, "tpu") == "grouped"
    for held, K in ((16, 2), (256, 8), (64, 6)):
        assert form(held, 8, K, "tpu") == "zero_gate"     # decode
        assert form(held, 8192, K, "cpu") == "zero_gate"


def test_yarn_frequencies_and_scale_at_published_widths():
    """DeepSeek-V2-Lite's YaRN: over 64 rope dims, base 10000, 4096
    original positions and beta 32 / 1, the correction range is dims
    10..23 of 32 frequencies: the plain frequency below it, a fortieth of
    it above, a linear blend between. The softmax scale gains
    mscale(40, 0.707)^2 = (0.1 * 0.707 * ln 40 + 1)^2."""
    cfg = get_config("deepseek-v2-lite-16b")
    inv = np.asarray(yarn_inv_freq(64, 1e4, 40.0, 4096, 32.0, 1.0))
    plain = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, plain / 40 * ramp + plain * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40.0, 0.707) == pytest.approx(m)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert (cfg.yarn_factor, cfg.norm_topk_prob, cfg.norm_eps) == (
        40.0, False, 1e-6)


def test_reduced_deepseek_shares_prefix_pages():
    """Drop-free routing makes a prompt prefix's cache independent of its
    suffix, so a MoE model's paged cache shares prefix pages: the token
    streams equal the dense cache's, which shares nothing."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").reduced(),
                              num_experts=8, experts_held=4, first_expert=2,
                              top_k=3, vocab_size=1000)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = build_model(cfg, plan_from_mesh(mesh)).init(
        jax.random.PRNGKey(3))
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size,
                                               8).astype(np.int32)
    reqs = [(prompt, 6), (prompt, 3), (prompt, 3), (prompt, 4)]
    kw = dict(params=params, mesh=mesh, num_groups=2, group_size=1,
              max_prompt_len=8, max_new_tokens=6, cache_len=24)
    dense = api.compile(cfg, mode="serve", backend="monolithic", **kw)
    ref = dense.generate(reqs)
    shr = api.compile(cfg, mode="serve", backend="monolithic",
                      cache="paged", page_len=4, num_pages=16, **kw)
    outs = shr.generate(reqs)
    for i, (got, want) in enumerate(zip(outs, ref)):
        assert np.array_equal(got, want), f"request {i}"
    assert shr.share_prefix and shr.last_stats["shared_pages"] > 0
    assert shr.last_stats["moe_pairs"] > 0
