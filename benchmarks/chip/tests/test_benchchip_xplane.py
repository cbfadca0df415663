"""The trace reduction: busy and idle time, per-module device time, top
operations and idle gaps named by the host span open during them."""
import pathlib

import pytest

import benchchip_tiny  # noqa: F401  (puts the harness on sys.path)
from benchchip import xplane

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "tiny.xplane.pb"


def test_union_and_gaps():
    busy = xplane.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert xplane.gaps_between(busy, -1.0, 5.0) == [
        (-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert xplane.clip(busy, 1.0, 3.5) == [(1.0, 2.0), (3.0, 3.5)]


def test_reduce_synthetic_events():
    """Two device planes; spans cover [0, 10]. Device 0 busy 0-4 and 6-7,
    device 1 busy 2-5: busy is averaged over the planes."""
    dev0 = {"modules": [("jit_local_decode(7)", 0.0, 4.0),
                        ("jit_gather(3)", 6.0, 7.0)],
            "ops": [("fusion.1", 0.0, 3.0), ("fusion.2", 3.0, 4.0),
                    ("copy.1", 6.0, 7.0)]}
    dev1 = {"modules": [("jit_local_decode(7)", 2.0, 5.0)],
            "ops": [("fusion.1", 2.0, 5.0)]}
    spans = [("bench.generate", 0.0, 5.0), ("bench.wait", 5.0, 8.0),
             ("bench.generate", 8.0, 10.0)]
    red = xplane.reduce([dev0, dev1], spans, top=3)
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx((5.0 + 3.0) / 2)
    assert red["modules"] == {"jit_local_decode": 7.0, "jit_gather": 1.0}
    assert red["top_ops"][0] == ("jit_local_decode/fusion.1", 6.0)
    assert ("jit_gather/copy.1", 1.0) in red["top_ops"]
    # longest gaps: device 1's 5-10 (5 s, mostly waiting: midpoint 7.5),
    # device 0's 7-10 (3 s, midpoint 8.5 inside the second call)
    assert red["idle_gaps"][0] == ["bench.wait", 5.0]
    assert red["idle_gaps"][1] == ["bench.generate", 3.0]
    assert xplane.module_seconds(red, [r"local_decode"]) == 7.0
    assert xplane.module_seconds(red, [r"prefill"]) is None


def test_reduce_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError, match="no TPU device plane"):
        xplane.reduce([], [("bench.generate", 0.0, 1.0)])


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: two jitted functions, three runs of
    the first and two of the second inside two ``bench.generate`` spans
    around a ``bench.wait``. The device clock runs about 1 ms behind the
    host's here, so the first two executions fall before the first span
    and are left out of the window."""
    devices, spans = xplane.read_events(str(FIXTURE))
    assert len(devices) == 1
    assert len(devices[0]["modules"]) == 5 and len(devices[0]["ops"]) == 11
    assert [n for n, _, _ in spans] == ["bench.generate", "bench.wait",
                                        "bench.generate"]
    red = xplane.reduce(devices, spans)
    assert red["window_s"] == pytest.approx(spans[-1][2] - spans[0][1])
    assert 0.0 < red["busy_s"] < 0.01 * red["window_s"]
    assert set(red["modules"]) == {"jit__lambda"}
    assert all(name.startswith("jit__lambda/%") for name, _ in red["top_ops"])
    assert red["busy_s"] == pytest.approx(sum(t for _, t in red["top_ops"]))
    label, longest = red["idle_gaps"][0]
    assert label == "bench.wait" and longest > 0.5 * (spans[1][2] - spans[1][1])

