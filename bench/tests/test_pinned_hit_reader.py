"""The reader of the pinned-stack hit share, on hand-made recordings:
a known ratio where the program recorded the counters, 0 where no
query launched a kernel, and ``None`` where the program keeps no
recording or no ``dispatch_pinned_hits`` counter."""
import json
from types import SimpleNamespace

import pytest

import repro.obs.telemetry
from bench import harness

NAME = "dispatch.pinned_hit_pct"


def recorded(monkeypatch, *args):
    """A run whose window is one root ``query`` span per dict of
    counters in ``args``."""
    spans = [SimpleNamespace(span_id=k, name="query", start=10.0 * k,
                             end=10.0 * k + 1.0, parent_id=None, args=a)
             for k, a in enumerate(args)]
    telemetry = SimpleNamespace(tracer=SimpleNamespace(spans=spans))
    monkeypatch.setattr(repro.obs.telemetry, "last_recording",
                        lambda: telemetry)
    return harness.Run(cell={"name": "geo.hot", "chips": 1}, config={},
                       setup_s=1.0, window_s=1.0,
                       records=[{} for _ in args], compiles=0, peak={})


def read(run):
    return harness.load_reader(NAME)(run)


def test_reader_is_in_the_benchmark():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (metric,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert metric["workloads"] == ["ptf.ptf2", "geo.sweep", "geo.sweep.x4",
                                   "geo.hot"]


def test_known_ratio(monkeypatch):
    run = recorded(monkeypatch,
                   {"dispatch_launches": 6, "dispatch_pinned_hits": 0},
                   {"dispatch_launches": 2, "dispatch_pinned_hits": 2})
    assert read(run) == pytest.approx(25.0)


def test_zero_without_launches(monkeypatch):
    zero = {"dispatch_launches": 0, "dispatch_pinned_hits": 0}
    assert read(recorded(monkeypatch, zero, zero)) == 0


def test_none_without_the_counter(monkeypatch):
    """A program whose ``query`` spans lack the counter (the one before
    it) leaves the metric out."""
    run = recorded(monkeypatch, {"dispatch_launches": 3},
                   {"dispatch_launches": 1})
    assert read(run) is None


def test_none_without_a_recording(monkeypatch):
    run = recorded(monkeypatch)
    monkeypatch.delattr(repro.obs.telemetry, "last_recording")
    assert read(run) is None
