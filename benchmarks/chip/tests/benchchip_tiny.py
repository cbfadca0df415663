"""Tiny cells for CPU tests: the benchmark's two configurations with their
widths cut to a few dozen, the same files and code paths otherwise."""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchchip import registry  # noqa: E402


def run_module():
    """``run.py`` as a module (it is a script, not a package member)."""
    return registry.load_module(HERE / "run.py", "benchchip_run")


def tiny_files(kind: str, **serve):
    """``CellFiles`` of a tiny dense (``"qwen"``, backlog) or SSM
    (``"mamba"``, open loop) cell; ``serve`` adds to its serving options."""
    R = run_module()
    if kind == "qwen":
        spec = registry.config_spec("qwen3-1.7b")
        spec.update(num_hidden_layers=2, hidden_size=64,
                    intermediate_size=128, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, vocab_size=8192)
        mod = registry.config_module("qwen3-1.7b")
        mix = {"loop": "backlog", "rate_per_s": 4.0,
               "prompt": {"dist": "uniform", "min": 16, "max": 32,
                          "multiple": 16},
               "output": {"dist": "uniform", "min": 16, "max": 24}}
    else:
        spec = registry.config_spec("mamba2-370m")
        spec.update(d_model=64, n_layer=2, vocab_size=8000)
        spec["ssm_cfg"] = dict(spec["ssm_cfg"], d_state=16, headdim=16,
                               chunk_size=32)
        mod = registry.config_module("mamba2-370m")
        mix = {"loop": "open", "rate_per_s": 20.0,
               "arrival": {"dist": "gamma", "cv": 2.0},
               "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                          "min": 16, "max": 32, "multiple": 16},
               "output": {"dist": "lognormal", "median": 5, "sigma": 0.5,
                          "min": 3, "max": 8}}
    workload = {"name": f"tiny-{kind}", "config": kind, "traffic": kind,
                "chips": 1}
    cell = {"serve": {"num_groups": 2, "group_size": 2, "cache_len": 64,
                      **serve},
            "check": {"tokens": 160, "max_requests": 16,
                      "limits": {"mean_logit_gap": TINY_LIMIT}}}
    return R.CellFiles(workload, spec, mod, mix, cell)


#: the tiny cells' limit on the mean logit gap. At these widths, over
#: about 160 checked tokens on eight seeds each, the bfloat16 program read
#: at most 0.000376 (qwen) and 0.000182 (mamba); the float8 control read at
#: least 0.00383 and 0.00472
TINY_LIMIT = 0.0012


def measure(kind: str, seed: int, seconds: float = 1.0, metrics=(),
            **serve):
    """One CPU run of a tiny cell with the given metric names."""
    import time

    import jax

    from benchchip.peaks import PEAKS

    R = run_module()
    return R.measure(tiny_files(kind, **serve), [{"name": n, "unit": "x"}
                                        for n in metrics],
                     seed=seed, seconds=seconds, trace=False,
                     devices=jax.devices(), peaks=PEAKS["TPU v5 lite"],
                     t_start=time.perf_counter())
