"""The traffic generator: seeded, stratified, bucketed, bursty as stated."""
import numpy as np
import pytest

import benchchip_tiny  # noqa: F401  (puts the harness on sys.path)
from benchchip import registry
from benchchip.traffic import Traffic, buckets, gaps

SEED = 3_000_000_017          # past 32 signed bits, as a run's seed may be


RATE = 8.0                    # a mix's rate comes from its cell


def _mix(name, rate=RATE):
    return dict(registry.traffic_mix(name), rate_per_s=rate)


@pytest.mark.parametrize("name", ["longdoc-batch", "chat-burst"])
def test_same_seed_same_requests(name):
    def draw(seed):
        t = Traffic(_mix(name), vocab=1000, seed=seed, slots=8)
        reqs = t.schedule(30.0)
        return [(r.due, r.gen, r.prompt.tolist()) for r in reqs]
    assert draw(SEED) == draw(SEED)
    assert draw(SEED) != draw(SEED + 1)


@pytest.mark.parametrize("name,reordered", [("longdoc-batch", False),
                                            ("chat-burst", True)])
def test_seeds_share_one_multiset_of_sizes(name, reordered):
    """Another seed draws the same prompt and output lengths, so every run
    of a cell does the same work: in another order in an open loop, in the
    one fixed order in a backlog (whose makespan depends on it)."""
    def sizes(seed):
        t = Traffic(_mix(name), vocab=1000, seed=seed, slots=8)
        reqs = t.schedule(30.0)
        return (sorted(r.prompt.size for r in reqs),
                sorted(r.gen for r in reqs),
                [(r.prompt.size, r.gen) for r in reqs])
    a, b = sizes(SEED), sizes(SEED + 99)
    assert a[:2] == b[:2]
    assert (a[2] != b[2]) == reordered


@pytest.mark.parametrize("name", ["longdoc-batch", "chat-burst"])
def test_lengths_only_in_declared_buckets(name):
    mix = _mix(name)
    t = Traffic(mix, vocab=1000, seed=SEED, slots=16)
    reqs = t.schedule(60.0)
    allowed = set(buckets(mix["prompt"]))
    assert {r.prompt.size for r in reqs} <= allowed
    assert all(mix["output"]["min"] <= r.gen <= mix["output"]["max"]
               for r in reqs)
    assert all((0 <= r.prompt).all() and (r.prompt < 1000).all()
               for r in reqs)


def test_bucket_counts():
    assert buckets(_mix("longdoc-batch")["prompt"]) == list(
        range(1024, 2049, 128))                       # 9 buckets
    assert buckets(_mix("chat-burst")["prompt"]) == list(
        range(128, 1025, 128))                        # 8 buckets


def test_backlog_is_due_at_once_and_fills_every_slot():
    """A backlog window is rate x seconds requests, all due when it opens,
    and never fewer than the cell's slots."""
    rate = registry.cell("qwen3-1.7b.serve.longdoc-batch")["load"][
        "rate_per_s"]
    t = Traffic(_mix("longdoc-batch", rate), vocab=1000, seed=SEED, slots=8)
    reqs = t.schedule(50.0)
    assert len(reqs) == round(rate * 50.0)
    assert {r.due for r in reqs} == {0.0}
    t = Traffic(_mix("longdoc-batch", rate), vocab=1000, seed=SEED, slots=8)
    assert len(t.schedule(1.0)) == 8


#: the stratified Gamma gaps reproduce the stated coefficient of variation
#: to within 10% at 2,000 arrivals (the midpoint quantiles trim the far tail)
CV_TOL = 0.10


@pytest.mark.parametrize("cv", [1.0, 2.0])
def test_gamma_gaps_mean_and_cv(cv):
    g = gaps({"dist": "gamma", "cv": cv}, 10.0, 2000,
             np.random.default_rng(SEED))
    assert g.sum() == pytest.approx(200.0)
    assert g.mean() == pytest.approx(0.1)
    assert abs(g.std() / g.mean() - cv) <= CV_TOL * cv


def test_open_loop_schedule_inside_the_window():
    mix = _mix("chat-burst")
    t = Traffic(mix, vocab=1000, seed=SEED, slots=32)
    reqs = t.schedule(30.0)
    due = [r.due for r in reqs]
    assert len(reqs) == int(RATE * 30.0)
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 30.0


#: long and short prompts in one window, as a mixed long/short mix states it
MIXED = {"loop": "open", "rate_per_s": 4.0,
         "arrival": {"dist": "gamma", "cv": 1.0},
         "prompt": {"dist": "mixture", "parts": [
             {"weight": 3, "dist": "uniform", "min": 128, "max": 256,
              "multiple": 128},
             {"weight": 1, "dist": "uniform", "min": 2048, "max": 4096,
              "multiple": 1024}]},
         "output": {"dist": "uniform", "min": 16, "max": 64}}


def test_mixture_gives_each_part_its_share():
    t = Traffic(MIXED, vocab=1000, seed=SEED, slots=8)
    reqs = t.schedule(10.0)
    lens = [r.prompt.size for r in reqs]
    assert len(lens) == 40
    assert sum(n <= 256 for n in lens) == 30
    assert set(lens) <= set(buckets(MIXED["prompt"]))
    assert buckets(MIXED["prompt"]) == [128, 256, 2048, 3072, 4096]
    assert t.max_prompt() == 4096 and t.max_gen() == 64


def test_shared_prefix_is_dealt_out_in_turn():
    """Every prompt starts with one of the mix's shared prefixes, each as
    often as the others; a seed draws the same prefixes every time."""
    mix = dict(_mix("longdoc-batch", 0.32),
               prefix={"tokens": 512, "count": 2})

    def draw(seed):
        return Traffic(mix, vocab=1000, seed=seed, slots=8).schedule(50.0)
    reqs = draw(SEED)
    heads = [r.prompt[:512].tobytes() for r in reqs]
    assert len(set(heads)) == 2
    assert heads.count(heads[0]) == len(reqs) // 2
    assert len({r.prompt[512:].tobytes() for r in reqs}) == len(reqs)
    assert heads == [r.prompt[:512].tobytes() for r in draw(SEED)]
    no_prefix = Traffic(_mix("longdoc-batch", 0.32), vocab=1000, seed=SEED,
                        slots=8).schedule(50.0)
    assert [r.prompt.size for r in no_prefix] == [r.prompt.size
                                                  for r in reqs]
    with pytest.raises(ValueError, match="shared prefix"):
        Traffic(dict(mix, prefix={"tokens": 4096, "count": 1}), vocab=1000,
                seed=SEED, slots=8)
