"""The two GEO cells beside ``geo.sweep``: ``geo.sweep.x4`` serves the
same cells and boxes on four chips, and ``geo.hot`` is the paper's
five-position GEO workload, whose working set fits the budget."""
import json

import numpy as np

from bench import harness

SMALL = {"n_files": 4, "n_seeds": 400, "clones_per_seed": 10}


def inputs(cell, seed):
    _, c, config, mix = harness.load_cell(cell)
    config = {**config, **SMALL}
    files, anchors = harness.load_module(
        "datasets", config["generator"]).generate(config, seed)
    warm, passes = harness.load_module("generators", mix["generator"]).build(
        mix, config, seed, anchors)
    return c, config, files, warm, passes


def test_x4_differs_from_geo_only_in_the_layout():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    geo, x4 = (json.loads((harness.ROOT / files[n]).read_text())
               for n in ("geo", "geo_x4"))
    differ = {k for k in set(geo) | set(x4) if geo.get(k) != x4.get(k)}
    assert differ == {"name", "source", "deployment", "reduced", "assumed"}
    assert set(x4["reduced"]) == set(geo["reduced"])
    assert set(x4["assumed"]) - set(geo["assumed"]) == {"node_to_chip"}


def test_x4_serves_the_same_inputs_on_four_chips():
    seed = 2**31 + 91
    c4, _, f4, w4, s4 = inputs("geo.sweep.x4", seed)
    c1, _, f1, w1, s1 = inputs("geo.sweep", seed)
    assert (c4["chips"], c1["chips"]) == (4, 1)
    assert w4 == w1 and s4[:3] == s1[:3]
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(f4, f1))


def test_geo_hot_is_five_positions_there_and_back():
    cell, config, _, warm, stream = inputs("geo.hot", 3)
    assert cell["chips"] == 1
    one = stream[0]
    assert len(one) == 10 and one == one[:5] + one[:5][::-1]
    assert warm == one and len(stream) * len(one) >= 20_000
    hi = config["domain_hi"]
    lo = np.array([b[0] for b in one[:5]])
    top = np.array([b[1] for b in one[:5]])
    span = (top.max(axis=0) - lo.min(axis=0) + 1) / np.array(hi)
    # 12% of longitude by 12% + 4 steps of 6% of latitude: 4.3% of the
    # domain, inside the 10% budget.
    assert np.allclose(span, [0.12, 0.36], atol=1e-4)
    assert span.prod() < config["budget_frac"]
