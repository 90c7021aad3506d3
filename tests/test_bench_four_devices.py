"""The benchmark's four-chip cell, ``geo.sweep.x4`` (the paper's 8 GEO
workers over four chips, two nodes a chip), run by the benchmark's own
harness at a small size on four CPU devices: its answers are correct
and equal to those of the same boxes with every node on one device,
the control is not correct, ship moves bytes between devices and the
launches of a query spread over devices."""
import time
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]
SMALL = {"n_files": 8, "n_seeds": 2000, "clones_per_seed": 10}
SEED = 2715000777


@pytest.fixture
def run(monkeypatch):
    """``run(cell, answer=None)``: one small run of ``cell`` through
    ``bench.harness`` on the CPU; returns its result and the window's
    records, each also holding the program's ``ExecutedQuery`` under
    ``"executed"``."""
    monkeypatch.syspath_prepend(str(ROOT))
    from bench import harness
    record, serve = harness.record, harness.serve
    window = []

    def keep(box, latency_s, e):
        return {**record(box, latency_s, e), "executed": e}

    def serve_and_keep(*args):
        records, window_s = serve(*args)
        window.extend(records)
        return records, window_s
    monkeypatch.setattr(harness, "record", keep)
    monkeypatch.setattr(harness, "serve", serve_and_keep)

    def one(cell, answer=None):
        window.clear()
        result = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                                  platform="cpu", config_override=SMALL,
                                  answer=answer)
        return result, list(window)
    return one


def test_geo_sweep_x4_on_four_devices(run, rerun_on_cpu_devices):
    if len(jax.devices()) < 4:
        rerun_on_cpu_devices(
            f"{__file__}::test_geo_sweep_x4_on_four_devices", 4)
        return
    from bench import control
    four, records = run("geo.sweep.x4")
    assert four["device"]["count"] == 4
    assert four["correct"] and four["failed"] == 0 and four["attempted"] > 0
    assert sum(r["ship_bytes"] for r in records) > 0
    assert max(r["executed"].dispatch_devices for r in records) > 1

    strict, _ = run("geo.sweep.x4", answer=control.strict_count)
    assert not strict["correct"]
    assert strict["checks"]["wrong_answers"]["value"] > 0

    one, one_records = run("geo.sweep")
    assert one["device"]["count"] == 1 and one["correct"]
    assert sum(r["ship_bytes"] for r in one_records) == 0
    answers = {r["box"]: r["matches"] for r in records}
    assert answers and answers == {r["box"]: r["matches"]
                                   for r in one_records}
