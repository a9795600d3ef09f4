"""The trace reduction on a trace written by hand: busy and idle time,
collective time and its exposed share, and idle gaps credited to the host
span they fell in."""
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace as T  # noqa: E402
from chipbench.cell import metric_reader  # noqa: E402
from chipbench.harness import RunRecord  # noqa: E402


def fixture_trace() -> T.Trace:
    return T.Trace(
        device_ops={
            0: [("fusion.1", 100, 200), ("collective-permute-start.1", 200, 210),
                ("collective-permute-done.1", 210, 260), ("fusion.2", 240, 300),
                ("all-gather.3", 400, 450)],
            # fusion.0 starts before the window and is clipped to it
            1: [("fusion.0", 0, 120), ("fusion.1", 100, 300), ("reduce-scatter.2", 300, 350)],
        },
        host_spans=[(T.WINDOW_SPAN, 50, 500), ("next_batch", 300, 380),
                    ("dispatch", 380, 395), ("wait", 395, 500),
                    ("next_batch", 0, 40)],
    )


def test_interval_arithmetic():
    assert T.union([(5, 9), (1, 3), (2, 4), (9, 10)]) == [(1, 4), (5, 10)]
    assert T.clip([(0, 10), (20, 30), (40, 50)], 5, 25) == [(5, 10), (20, 25)]
    assert T.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert T.total([(0, 2), (5, 9)]) == 6


def test_collective_names():
    for name in ("all-gather.3", "all-gather-start.1", "reduce-scatter.2",
                 "collective-permute-done.7", "all-reduce.1", "all-to-all"):
        assert T.is_collective(name), name
    for name in ("fusion.1", "copy-start.2", "convolution.4", "gather.1"):
        assert not T.is_collective(name), name


def test_reduce_busy_idle_collectives():
    r = T.reduce(fixture_trace())
    assert r["devices"] == 2
    assert math.isclose(r["window_s"], 450e-9)
    # device 0: busy [100,300] + [400,450]; device 1: [50,350]
    assert math.isclose(r["busy_s"], (250 + 300) / 2 * 1e-9)
    # device 0: collectives [200,260] + [400,450], of which [240,260] overlaps
    # fusion.2; device 1: [300,350], nothing beside it
    assert math.isclose(r["collective_s"], (110 + 50) / 2 * 1e-9)
    assert math.isclose(r["exposed_collective_s"], (90 + 50) / 2 * 1e-9)


def test_idle_gaps_credited_to_host_spans():
    r = T.reduce(fixture_trace())
    gaps = [(name, round(s * 1e9)) for name, s in r["idle_gaps"]]
    # device 1 idles [350,500] mostly in "wait"; device 0 idles [300,400]
    # mostly in "next_batch", [450,500] in "wait", and [50,100] in no span
    assert gaps == [("wait", 150), ("next_batch", 100), ("wait", 50), ("host", 50)]


def test_top_ops_are_averaged_over_devices():
    r = T.reduce(fixture_trace(), top=2)
    top = [(n, round(s * 1e9)) for n, s in r["device_ops"]]
    assert top == [("fusion.1", 150), ("fusion.0", 35)]


def test_window_span_required():
    tr = fixture_trace()
    tr.host_spans = [s for s in tr.host_spans if s[0] != T.WINDOW_SPAN]
    with pytest.raises(ValueError):
        T.reduce(tr)


def test_metric_readers_on_the_fixture():
    red = T.reduce(fixture_trace())
    run = RunRecord(steps=2, tokens_per_step=1000, window_s=2.0, chips=4,
                    useful_flops_per_token=1e9, peak={"bf16_flops": 1e12},
                    spans={"next_batch": [0.002, 0.004]}, device=red)
    read = {n: metric_reader(n).read(run) for n in (
        "device_idle_pct", "collective_ms_per_step", "exposed_collective_pct",
        "host_batch_ms", "mfu")}
    assert math.isclose(read["device_idle_pct"], 100 * (1 - 275 / 450))
    assert math.isclose(read["collective_ms_per_step"], 1e3 * 80e-9 / 2)
    assert math.isclose(read["exposed_collective_pct"], 100 * 70 / 80)
    assert math.isclose(read["host_batch_ms"], 3.0)
    assert math.isclose(read["mfu"], 100 * 1e9 * 1000 * 2 / (2.0 * 4 * 1e12))


def test_readers_return_nothing_without_a_reading():
    red = dict(T.reduce(fixture_trace()), collective_s=0.0, exposed_collective_s=0.0)
    run = RunRecord(steps=2, tokens_per_step=1, window_s=1.0, chips=1,
                    useful_flops_per_token=1.0, peak={"bf16_flops": 1.0},
                    spans={}, device=red)
    assert metric_reader("collective_ms_per_step").read(run) is None
    assert metric_reader("exposed_collective_pct").read(run) is None
    assert metric_reader("host_batch_ms").read(run) is None
