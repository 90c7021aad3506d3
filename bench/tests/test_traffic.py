"""The datasets and the traffic generator, found by name as a run finds
them: the same seed gives the same inputs, and seeds change where cells
and boxes lie, not the amount of work."""
import numpy as np
import pytest

from bench import harness

SMALL = {"ptf": {"n_files": 6, "cells_per_file_mean": 3000},
         "geo": {"n_files": 4, "n_seeds": 400, "clones_per_seed": 10}}


def inputs(cell, seed):
    _, c, config, mix = harness.load_cell(cell)
    config = {**config, **SMALL[c["config"]]}
    files, anchors = harness.load_module(
        "datasets", config["generator"]).generate(config, seed)
    warm, passes = harness.load_module("generators", mix["generator"]).build(
        mix, config, seed, anchors)
    return config, mix, files, warm, passes


@pytest.mark.parametrize("cell", ["ptf.ptf2", "geo.sweep"])
def test_same_seed_same_inputs(cell):
    _, _, f1, w1, s1 = inputs(cell, 2**31 + 77)
    _, _, f2, w2, s2 = inputs(cell, 2**31 + 77)
    assert w1 == w2 and s1[:100] == s2[:100]
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(f1, f2))


@pytest.mark.parametrize("cell", ["ptf.ptf2", "geo.sweep"])
def test_seed_keeps_the_work(cell):
    """Every seed serves the same cells and boxes, so the program plans,
    chunks and compiles the same work; the seed orders each file's rows
    and draws the attributes."""
    runs = [inputs(cell, seed) for seed in (11, 2**31 + 5)]
    (config, _, f1, w1, s1), (_, _, f2, w2, s2) = runs
    assert w1 == w2 and s1[:3] == s2[:3]
    for (c1, a1), (c2, a2) in zip(f1, f2):
        assert np.all(c1 >= 1) and np.all(c1 <= config["domain_hi"])
        assert np.array_equal(np.unique(c1, axis=0), np.unique(c2, axis=0))
        assert not np.array_equal(c1, c2) and not np.array_equal(a1, a2)


def test_ptf2_alternates_four_shifted_fields():
    config, mix, _, warm, stream = inputs("ptf.ptf2", 3)
    hi = config["domain_hi"]
    assert len(stream[0]) == 4 and len(set(stream[0])) == 4
    assert stream[:2] == [stream[0]] * 2
    assert warm == stream[0] * mix["warmup_cycles"]
    (lo0, hi0), (lo1, _) = stream[0][0], stream[0][1]
    assert hi0[0] - lo0[0] + 1 == int(hi[0] * 0.16)
    assert (lo0[2], hi0[2]) == (1, hi[2])          # all of time
    assert lo1[0] - lo0[0] == int(hi[0] * 0.16) // 3


def test_geo_sweep_goes_there_and_back():
    config, mix, _, warm, stream = inputs("geo.sweep", 3)
    n = mix["positions"]
    one = stream[0]
    assert len(one) == 2 * n and stream[1] == one
    assert one == one[:n] + one[:n][::-1] and warm == one
    lats = [b[0][1] for b in one[:n]]
    step = int(config["domain_hi"][1] * 0.06)
    assert np.all(np.diff(lats) == step)
    assert len({b[0][0] for b in one}) == 1         # longitude fixed


def test_window_closes_at_the_end_of_a_pass(monkeypatch):
    from types import SimpleNamespace
    clock = [0.0]

    def run_query(q):
        clock[0] += 1.0
        report = SimpleNamespace(queried_cells=1, opt_time_chunking_s=0.0,
                                 opt_time_evict_place_s=0.0,
                                 files_scanned=[], evicted_items=0)
        return SimpleNamespace(report=report, matches=0, degraded=None,
                               prep_s=0.0, dispatch_s=0.0, measured_net_s=0.0,
                               measured_ship_bytes=0, artifact_hits=0,
                               artifact_misses=0)

    monkeypatch.setattr(harness, "time",
                        SimpleNamespace(perf_counter=lambda: clock[0]))
    one = [((1, 1), (2, 2)), ((3, 3), (4, 4)), ((5, 5), (6, 6))]
    records, window_s = harness.serve(SimpleNamespace(run_query=run_query),
                                      [one] * 5, 300, 4.0)
    assert [r["box"] for r in records] == one * 2 and window_s == 6.0
