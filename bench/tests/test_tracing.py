"""The trace reduction on a hand-made trace whose answers are known."""
import pytest

from bench import tracing

NS = 1e-9


def planes():
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [[tracing.WINDOW, 1000.0, 9000.0]]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_x", 0.0, 20000.0]]},
        {"name": tracing.OPS_LINE, "events": [
            ["simjoin_pruned", 500.0, 1000.0],    # starts before the window
            ["copy", 1600.0, 200.0],
            ["simjoin_dense", 5000.0, 1000.0],
            ["fusion", 9500.0, 1000.0]]}]}        # ends after it
    return [host, dev]


def test_reduce_busy_kernel_and_idle():
    spans = [("query", 1500.0, 9000.0), ("prep", 3000.0, 4000.0)]
    r = tracing.reduce(planes(), "simjoin", spans)
    assert r["window_s"] == pytest.approx(9000 * NS)
    # busy: [1000, 1500] + [1600, 1800] + [5000, 6000] + [9500, 10000]
    assert r["busy_s"] == pytest.approx(2200 * NS)
    assert r["kernel_s"] == pytest.approx(1500 * NS)
    assert dict(r["device_ops"]) == pytest.approx(
        {"simjoin_dense": 1000 * NS, "copy": 200 * NS,
         "simjoin_pruned": 500 * NS, "fusion": 500 * NS})
    # idle [1800, 5000] has its midpoint in prep; [1500, 1600] and
    # [6000, 9500] theirs in query
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"query": 3600 * NS, "prep": 3200 * NS})


def test_reduce_averages_over_devices():
    two = planes() + [{"name": "/device:TPU:1", "lines": [
        {"name": tracing.OPS_LINE, "events": [["simjoin_dense", 2000.0,
                                               4000.0]]}]}]
    r = tracing.reduce(two, "simjoin", devices=2)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((2200 + 4000) / 2 * NS)
    assert r["kernel_s"] == pytest.approx(5500 * NS)


def test_innermost_sweep():
    spans = [("a", 0.0, 10.0), ("b", 2.0, 4.0), ("c", 3.0, 3.5),
             ("d", 6.0, 8.0)]
    got = tracing.innermost(spans, [3.2, 1.0, 5.0, 7.0, 11.0, 2.5])
    assert got == ["c", "a", "a", "d", "between queries", "b"]


def test_window_is_required():
    with pytest.raises(ValueError):
        tracing.window_ns([{"name": "/device:TPU:0", "lines": []}])


def test_self_times_subtract_nested_events():
    events = [["while", 0.0, 100.0], ["simjoin_pruned", 10.0, 30.0],
              ["simjoin_pruned", 50.0, 40.0], ["copy", 120.0, 10.0]]
    got = tracing.self_times(events, 0.0, 125.0)
    assert sorted(got) == [("copy", 5.0), ("simjoin_pruned", 30.0),
                           ("simjoin_pruned", 40.0), ("while", 30.0)]


def test_op_name():
    assert tracing.op_name("%simjoin_pruned.4 = s32[1]{0:T(128)} "
                           "custom-call(s32[768] %x.2)") == "simjoin_pruned"
    assert tracing.op_name("while") == "while"


def test_recorded_trace_against_a_raster():
    """300 ms of a traced PTF cross-match window on one TPU v5e: busy time
    and kernel time from the reduction agree with a 100 ns raster of
    the same events."""
    import json
    from pathlib import Path

    import numpy as np
    planes = json.loads((Path(__file__).parent / "data"
                         / "ptf_hot_trace_excerpt.json").read_text())
    lo, hi = tracing.window_ns(planes)
    ops = tracing.device_ops(planes)["/device:TPU:0"]
    r = tracing.reduce(planes, "simjoin")
    step = 100.0
    grid = np.zeros(int((hi - lo) / step) + 1, bool)
    kernel = 0.0
    for name, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) / step):int(np.ceil((b - lo) / step))] = True
            kernel += (b - a) if name.startswith("simjoin") else 0.0
    assert r["window_s"] == pytest.approx(0.3)
    assert r["busy_s"] == pytest.approx(grid.sum() * step * NS, rel=0.02)
    assert r["kernel_s"] == pytest.approx(kernel * NS)
    assert 0 < r["kernel_s"] <= r["busy_s"] < r["window_s"]
    names = dict(r["device_ops"])
    assert names["simjoin_pruned"] > 0.5 * r["busy_s"]
    assert sum(names.values()) <= r["busy_s"] * (1 + 1e-9)
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])
