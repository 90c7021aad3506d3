"""Every metric reader named in BENCHMARK.json, on a hand-made run."""
import json

import pytest

from bench import harness


def fake_run(traced):
    recs = [{"box": ((1, 1), (5, 5)), "latency_s": 0.01 * (k + 1),
             "matches": 3, "failed": False, "queried_cells": 1000,
             "opt_s": 0.002, "prep_s": 0.001 * k, "dispatch_s": 0.004,
             "net_s": 0.0, "ship_bytes": 0, "artifact_hits": 9,
             "artifact_misses": 1, "files_scanned": 0, "evicted": 0}
            for k in range(40)]
    trace = {"window_s": 2.0, "busy_s": 0.5, "kernel_s": 0.1} if traced \
        else None
    return harness.Run(cell={"name": "geo.sweep"},
                       config={"domain_hi": [100, 100]}, setup_s=12.5,
                       window_s=2.0, records=recs, compiles=0,
                       peak={"hbm_bytes_per_s": 819e9}, trace=trace)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_readers(kind):
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    run = fake_run(kind == "per_layer")
    for m in bench[kind]:
        value = harness.load_reader(m["name"])(run)
        assert isinstance(value, (int, float)) and value >= 0, m["name"]
    assert harness.load_reader("queries_per_s")(run) == 20.0
    if kind == "per_layer":
        assert harness.load_reader("device.idle_pct")(run) == 75.0
        assert 0 < harness.load_reader("simjoin_roofline")(run) < 100
    else:
        assert harness.load_reader("simjoin_roofline")(run) is None
