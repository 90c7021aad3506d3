"""The readers of ship time, bytes and transfers, of the launches'
spread over chips and of executables built, on hand-made recordings: a
value where the program recorded the span or counter, 0 where the work
did not happen, and ``None`` where the program keeps no recording or
no such counter."""
from types import SimpleNamespace

import pytest

import repro.obs.telemetry
from bench import harness

READERS = ("ship.ms", "ship.mb", "ship.transfers", "dispatch.chip_skew",
           "dispatch.backend_compiles")
COUNTERS = ("dispatch_launches", "dispatch_max_device_launches",
            "measured_ship_bytes", "ship_transfers",
            "dispatch_backend_compiles")


def span(sid, name, start, end, parent=None, **args):
    return SimpleNamespace(span_id=sid, name=name, start=start, end=end,
                           parent_id=parent, args=args)


def recorded(monkeypatch, spans, chips=4):
    """A run of two window queries whose program recorded ``spans``."""
    telemetry = SimpleNamespace(tracer=SimpleNamespace(spans=spans))
    monkeypatch.setattr(repro.obs.telemetry, "last_recording",
                        lambda: telemetry)
    return harness.Run(cell={"name": "geo.sweep.x4", "chips": chips},
                       config={}, setup_s=1.0, window_s=1.0,
                       records=[{}, {}], compiles=0, peak={})


def two_queries(first, second, ship_s=(0.010, 0.0)):
    """Two root ``query`` spans with counters ``first`` and ``second``,
    each with a ``ship`` span of ``ship_s`` seconds under it."""
    out = []
    for k, (args, ship) in enumerate(zip((first, second), ship_s)):
        t = 10.0 * (k + 1)
        out.append(span(2 * k, "query", t, t + 1.0, **args))
        out.append(span(2 * k + 1, "ship", t + 0.1, t + 0.1 + ship,
                        parent=2 * k))
    return out


def read(name, run):
    return harness.load_reader(name)(run)


def test_readers_are_in_the_benchmark():
    import json
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert set(READERS) <= set(per_layer)
    assert all("geo.sweep.x4" in per_layer[n]["workloads"] for n in READERS)


def test_values_where_recorded(monkeypatch):
    run = recorded(monkeypatch, two_queries(
        {"dispatch_launches": 6, "dispatch_max_device_launches": 3,
         "measured_ship_bytes": 2_000_000, "ship_transfers": 4,
         "dispatch_backend_compiles": 1},
        {"dispatch_launches": 2, "dispatch_max_device_launches": 2,
         "measured_ship_bytes": 0, "ship_transfers": 0,
         "dispatch_backend_compiles": 0}))
    assert read("ship.ms", run) == pytest.approx(5.0)
    assert read("ship.mb", run) == pytest.approx(1.0)
    assert read("ship.transfers", run) == pytest.approx(2.0)
    assert read("dispatch.chip_skew", run) == pytest.approx(5 / 8 * 4)
    assert read("dispatch.backend_compiles", run) == pytest.approx(0.5)


@pytest.mark.parametrize("chips, skew", [(1, 1.0), (4, 4.0)])
def test_chip_skew_bounds(monkeypatch, chips, skew):
    """Every launch on one chip reads the number of chips."""
    args = {"dispatch_launches": 5, "dispatch_max_device_launches": 5}
    run = recorded(monkeypatch, two_queries(args, args), chips=chips)
    assert read("dispatch.chip_skew", run) == pytest.approx(skew)


def test_zero_where_nothing_happened(monkeypatch):
    zero = dict.fromkeys(COUNTERS, 0)
    run = recorded(monkeypatch, two_queries(zero, zero, ship_s=(0.0, 0.0)))
    for name in READERS:
        assert read(name, run) == 0, name


def test_none_without_the_counters(monkeypatch):
    """A program whose ``query`` spans carry none of the new counters
    (the one before them) leaves those metrics out; its ``ship`` spans
    are still read."""
    run = recorded(monkeypatch, two_queries({"dispatch_launches": 3},
                                            {"dispatch_launches": 1}))
    assert read("ship.ms", run) == pytest.approx(5.0)
    for name in READERS[1:]:
        assert read(name, run) is None, name


def test_none_without_a_recording(monkeypatch):
    run = recorded(monkeypatch, [])
    monkeypatch.delattr(repro.obs.telemetry, "last_recording")
    for name in READERS:
        assert read(name, run) is None, name
