"""The work counts and the peaks table, against counts made by hand from the
published widths."""
import pytest

import benchchip_tiny  # noqa: F401  (puts the harness on sys.path)
from benchchip import registry
from benchchip.peaks import PEAKS, least_time, peaks_of


@pytest.fixture(scope="module")
def qwen():
    return registry.config_spec("qwen3-1.7b"), registry.config_module(
        "qwen3-1.7b")


@pytest.fixture(scope="module")
def mamba():
    return registry.config_spec("mamba2-370m"), registry.config_module(
        "mamba2-370m")


# qwen3-1.7b: 28 layers, d 2048, 16 q / 8 kv heads x 128, d_ff 6144,
# vocab 151936
QWEN_LAYER = (2048 * 16 * 128          # wq
              + 2 * 2048 * 8 * 128     # wk, wv
              + 16 * 128 * 2048        # wo
              + 3 * 2048 * 6144)       # gate, up, down
QWEN_HEAD = 2048 * 151936
QWEN_KV_TOKEN = 28 * 2 * 8 * 128 * 2   # k and v, every layer, bf16


def test_qwen_counts(qwen):
    spec, m = qwen
    assert QWEN_LAYER == 50_331_648
    assert m.matmul_params(spec) == 28 * QWEN_LAYER + QWEN_HEAD
    assert m.kv_bytes_per_token(spec) == QWEN_KV_TOKEN == 114_688
    # a token at context 1000: every projection, then q.k and p.v over 1000
    assert m.token_flops(spec, 1000) == (2 * (28 * QWEN_LAYER + QWEN_HEAD)
                                         + 28 * 2 * 2 * 16 * 128 * 1000)
    # weights read once per step: matrices and the 2 + 2 norms per layer
    wbytes = 2 * (28 * QWEN_LAYER + QWEN_HEAD
                  + 28 * (2 * 2048 + 2 * 128) + 2048)
    assert m.weight_bytes(spec) == wbytes
    flops, nbytes = m.decode_work(spec, [100, 200])
    assert flops == (2 * 2 * (28 * QWEN_LAYER + QWEN_HEAD)
                     + 28 * 4 * 16 * 128 * (100 + 200))
    assert nbytes == (wbytes + (100 + 200) * QWEN_KV_TOKEN
                      + 2 * QWEN_KV_TOKEN + 2 * (2 * 2048 + 4 * 151936))
    # causal prefill of 1024: projections for every token, the head once,
    # q.k and p.v over the lower triangle (1024 * 1025 / 2 pairs)
    assert m.prefill_flops(spec, 1024) == (
        2 * 28 * QWEN_LAYER * 1024 + 2 * QWEN_HEAD
        + 28 * 2 * 2 * 16 * 128 * (1024 * 1025 // 2))


# mamba2-370m: 48 layers, d 1024, d_inner 2048 = 32 heads x 64, state 128,
# conv 4, vocab 50277
MAMBA_LAYER = 1024 * (2 * 2048 + 2 * 128 + 32) + 2048 * 1024
MAMBA_HEAD = 1024 * 50277
MAMBA_STATE = 48 * (32 * 64 * 128 * 4 + 3 * (2048 + 2 * 128) * 2)


def test_mamba_counts(mamba):
    spec, m = mamba
    assert MAMBA_LAYER == 6_586_368
    assert m.matmul_params(spec) == 48 * MAMBA_LAYER + MAMBA_HEAD
    assert m.state_bytes(spec) == MAMBA_STATE == 50_995_200
    tok = (2 * (48 * MAMBA_LAYER + MAMBA_HEAD)
           + 48 * (2 * 4 * (2048 + 256) + 5 * 32 * 64 * 128))
    assert m.token_flops(spec) == tok == 799_057_920
    small = 48 * ((2048 + 256) * 4 + 3 * 32 + 2048 + 1024) + 1024
    wbytes = 2 * (48 * MAMBA_LAYER + MAMBA_HEAD + small)
    assert m.weight_bytes(spec) == wbytes
    flops, nbytes = m.decode_work(spec, [10, 900, 5])
    assert flops == 3 * tok
    assert nbytes == wbytes + 3 * (2 * MAMBA_STATE + 2 * 1024 + 4 * 50277)
    assert m.prefill_flops(spec, 256) == ((tok - 2 * MAMBA_HEAD) * 256
                                          + 2 * MAMBA_HEAD)


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks_of("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_of("cpu")


def test_least_time_is_the_binding_bound():
    p = PEAKS["TPU v5 lite"]
    assert least_time(197e12, 1.0, p) == pytest.approx(1.0)       # compute
    assert least_time(1.0, 819e9 * 2, p) == pytest.approx(2.0)    # memory


def test_decode_need_is_memory_bound_at_the_cells_sizes(qwen, mamba):
    """At decode, weights dominate: the least time is the bytes' bound, so
    a roofline share is bytes over bandwidth over device time."""
    p = PEAKS["TPU v5 lite"]
    for spec, m in (qwen, mamba):
        flops, nbytes = m.decode_work(spec, [2048] * 8)
        assert nbytes / p["hbm_bytes_per_s"] > flops / p["bf16_flops"]
