"""The unified telemetry layer (ISSUE 8): span nesting/parenting
invariants and Chrome trace export, the typed metrics registry's
emission-group semantics, the event channel that replaced the ad-hoc
pending-exec dict (including the leftover-events-after-the-last-query
fix), injectable clocks, and the two equivalence contracts — off-mode
summaries bit-identical to seed behavior, and the live registry's
``as_summary()`` equal to ``workload_summary`` on a mixed workload for
both backends."""
import json

import pytest

from repro.arrayio.catalog import FileReader, build_catalog
from repro.arrayio.generator import make_ptf_files
from repro.backend.base import (register_summary_counters, record_executed,
                                workload_summary)
from repro.core.cluster import RawArrayCluster
from repro.core.result_cache import ResultCache
from repro.core.workload import zipf_workload
from repro.obs import (Clock, EventChannel, ManualClock, MetricsRegistry,
                       MONOTONIC, NULL_REGISTRY, NULL_TELEMETRY, NULL_TRACER,
                       Telemetry, Tracer, as_clock, make_telemetry)

N_NODES = 4


@pytest.fixture(scope="module")
def ptf(tmp_path_factory):
    root = tmp_path_factory.mktemp("ptf_tel")
    files = make_ptf_files(n_files=8, cells_per_file_mean=700, seed=11)
    catalog, data = build_catalog(files, str(root), "fits", n_nodes=N_NODES)
    return catalog, data


def make_cluster(ptf, budget=400_000, **kw):
    catalog, data = ptf
    return RawArrayCluster(catalog, FileReader(catalog, data), N_NODES,
                           budget, policy="cost", min_cells=64, **kw)


def skewed(catalog, n_queries=18, seed=3):
    return zipf_workload(catalog.domain, n_queries=n_queries, n_templates=3,
                         s=1.5, eps=1, field_frac=0.25, seed=seed)


# ----------------------------------------------------------------- clock

def test_as_clock_adapters():
    assert as_clock(None) is MONOTONIC
    mc = ManualClock(start=5.0)
    assert as_clock(mc) is mc
    ticks = [1.0]
    wrapped = as_clock(lambda: ticks[0])
    assert isinstance(wrapped, Clock) and wrapped.now() == 1.0
    ticks[0] = 2.5
    assert wrapped.now() == 2.5
    with pytest.raises(TypeError):
        as_clock(42)


def test_manual_clock_advance_and_auto_step():
    mc = ManualClock(start=10.0, auto_step=0.5)
    assert mc.now() == 10.0
    assert mc.now() == 10.5
    mc.advance(4.0)
    assert mc.now() == 15.0
    with pytest.raises(ValueError):
        mc.advance(-1.0)
    frozen = ManualClock(start=3.0)
    assert frozen.now() == frozen.now() == 3.0


def test_monotonic_clock_advances():
    a = MONOTONIC.now()
    b = MONOTONIC.now()
    assert b >= a


# ---------------------------------------------------------------- tracer

def test_span_parenting_follows_open_stack():
    tr = Tracer(clock=ManualClock(auto_step=1.0))
    with tr.span("workload") as root:
        with tr.span("batch") as b:
            with tr.span("plan.scan") as s:
                pass
        with tr.span("dispatch") as d:
            pass
    assert root.parent_id is None
    assert b.parent_id == root.span_id
    assert s.parent_id == b.span_id
    assert d.parent_id == root.span_id          # sibling, not child of b
    assert all(sp.end is not None for sp in tr.spans)
    # parent intervals contain child intervals under the manual clock
    assert root.start <= b.start and b.end <= root.end
    assert b.start <= s.start and s.end <= b.end


def test_explicit_parent_override():
    tr = Tracer(clock=ManualClock(auto_step=1.0))
    root = tr.begin("workload")
    detached = tr.begin("recover", parent=root)
    inner = tr.begin("plan.scan")               # implicit: innermost open
    assert detached.parent_id == root.span_id
    assert inner.parent_id == detached.span_id
    tr.end(root)                                # closes descendants too
    assert inner.end is not None and detached.end is not None
    # innermost-first: children end no later than their parents
    assert inner.end <= detached.end <= root.end


def test_begin_end_pair_and_out_of_order_close():
    tr = Tracer(clock=ManualClock(auto_step=1.0))
    a = tr.begin("a")
    b = tr.begin("b")
    tr.end(a)                                   # b still open: closed first
    assert b.end is not None and b.end <= a.end
    c = tr.begin("c")
    tr.end(c)
    tr.end(c)                                   # double-end: no crash
    assert c.duration_s == 1.0
    assert a.duration_s > 0 and b.duration_s > 0


def test_open_span_duration_is_zero():
    tr = Tracer(clock=ManualClock(auto_step=1.0))
    s = tr.begin("open")
    assert s.duration_s == 0.0
    tr.end(s)
    assert s.duration_s == 1.0


def test_chrome_trace_shape(tmp_path):
    tr = Tracer(clock=ManualClock(start=100.0, auto_step=1.0), pid=7)
    with tr.span("workload", queries=2):
        with tr.span("query", cat="query"):
            pass
    doc = tr.to_chrome_trace()
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta, root_ev, child_ev = events
    assert meta["ph"] == "M" and meta["name"] == "process_name"
    assert root_ev["ph"] == "X" and root_ev["name"] == "workload"
    assert root_ev["ts"] == 0.0                 # normalized to earliest
    assert root_ev["pid"] == 7
    assert root_ev["args"]["queries"] == 2
    assert child_ev["cat"] == "query"
    assert child_ev["args"]["parent_id"] == root_ev["args"]["span_id"]
    assert child_ev["ts"] > 0 and child_ev["dur"] > 0
    path = tr.export(str(tmp_path / "t.trace.json"))
    assert json.load(open(path)) == doc


def test_null_tracer_is_inert():
    assert NULL_TRACER.begin("x") is None
    NULL_TRACER.end(None)
    with NULL_TRACER.span("x") as s:
        assert s is None
    assert NULL_TRACER.spans == []
    assert NULL_TRACER.to_chrome_trace() == {"traceEvents": [],
                                             "displayTimeUnit": "ms"}


# -------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc()
    c.inc(2.5)
    assert reg.counter("hits") is c and c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("util", node=1)
    g.set(0.75)
    assert reg.gauge("util", node=1).value == 0.75
    assert reg.gauge("util", node=2) is not g   # distinct label series
    h = reg.histogram("churn", bounds=(1, 4, 16))
    for v in (0, 1, 2, 100):
        h.observe(v)
    assert h.count == 4 and h.sum == 103
    assert sum(h.bucket_counts) == h.count
    assert h.bucket_counts[-1] == 1             # the overflow bucket


def test_registry_conflicts_rejected():
    reg = MetricsRegistry()
    reg.counter("x", group="a")
    with pytest.raises(ValueError):
        reg.counter("x", group="b")             # group fixed at creation
    with pytest.raises(ValueError):
        reg.gauge("x")                          # cross-kind collision
    reg.histogram("h", bounds=(1, 2))
    with pytest.raises(ValueError):
        reg.histogram("h", bounds=(1, 3))       # bounds must agree
    with pytest.raises(ValueError):
        reg.histogram("bad", bounds=(2, 1))     # not ascending


def test_as_summary_groups_and_order():
    reg = MetricsRegistry()
    reg.counter("always").inc(1)
    reg.counter("mqo_tasks_total", group="mqo").inc(5)
    reg.counter("replica_hits", group="replica").inc(2)
    assert reg.as_summary() == {"always": 1.0}  # no group marked yet
    reg.mark_group("mqo")
    summ = reg.as_summary()
    assert summ == {"always": 1.0, "mqo_tasks_total": 5.0}
    assert list(summ) == ["always", "mqo_tasks_total"]  # registration order
    assert all(isinstance(v, float) for v in summ.values())


def test_null_registry_is_inert():
    c = NULL_REGISTRY.counter("anything", group="g")
    c.inc(5)
    NULL_REGISTRY.gauge("g", node=0).set(1)
    NULL_REGISTRY.histogram("h", bounds=(1,)).observe(3)
    NULL_REGISTRY.mark_group("g")
    assert NULL_REGISTRY.as_summary() == {}


# --------------------------------------------------------- event channel

def test_event_channel_accumulates_and_mirrors():
    ch = EventChannel()
    assert ch.empty()
    ch.post("failover_readmits", 3)
    ch.post("failover_readmits", 2)
    ch.post("replicas_dropped")
    assert ch.peek() == {"failover_readmits": 5, "replicas_dropped": 1}
    assert not ch.empty()
    assert ch.drain() == {"failover_readmits": 5, "replicas_dropped": 1}
    assert ch.empty() and ch.drain() == {}


def test_telemetry_modes_and_make_telemetry():
    on = Telemetry("on", clock=ManualClock())
    assert on.enabled and isinstance(on.tracer, Tracer)
    off = make_telemetry("off")
    assert off is NULL_TELEMETRY is make_telemetry(None)
    assert not off.enabled
    assert off.tracer is NULL_TRACER and off.registry is NULL_REGISTRY
    assert make_telemetry(on) is on
    with pytest.raises(ValueError):
        make_telemetry("loud")
    with pytest.raises(ValueError):
        Telemetry("loud")


def test_off_mode_trace_export_is_wellformed(ptf, tmp_path):
    cl = make_cluster(ptf)                      # telemetry="off" default
    cl.run_workload(skewed(cl.catalog, n_queries=4))
    path = cl.export_trace(str(tmp_path / "off.trace.json"))
    assert json.load(open(path)) == {"traceEvents": [],
                                     "displayTimeUnit": "ms"}


# -------------------------------------------- equivalence: off == legacy

def test_off_mode_summary_bit_identical_to_on_mode(ptf):
    """With a frozen injected clock the numpy/simulated pipeline is fully
    deterministic, so telemetry on vs off must produce *bit-identical*
    summaries — instrumentation may not perturb a single counter or
    timing."""
    def run(mode):
        tel = Telemetry(mode, clock=ManualClock())
        cl = make_cluster(ptf, reuse="on", mqo="on", result_cache="on",
                          replication="hot", join_backend="numpy",
                          telemetry=tel)
        ex = cl.run_workload(skewed(cl.catalog), batch_size=6)
        return cl.summary(ex)

    s_off, s_on = run("off"), run("on")
    assert s_off == s_on
    assert list(s_off) == list(s_on)            # same key order too
    assert s_off["queries"] == 18.0


# ------------------------------------- equivalence: registry == summary

@pytest.mark.parametrize("backend", ["simulated", "jax_mesh"])
def test_live_registry_matches_workload_summary(ptf, backend):
    if backend == "jax_mesh":
        pytest.importorskip("jax")
    cl = make_cluster(ptf, reuse="on", mqo="on", result_cache="on",
                      replication="hot", join_backend="pallas",
                      backend=backend, telemetry="on")
    ex = cl.run_workload(skewed(cl.catalog, n_queries=24), batch_size=6)
    legacy = workload_summary(ex)
    live = cl.telemetry.registry.as_summary()
    assert live == legacy
    assert list(live) == list(legacy)
    # the mixed workload must actually engage the optional tiers
    assert legacy["mqo_tasks_total"] > 0
    assert legacy["queries"] == 24.0


def test_record_executed_incremental_equals_batch_fold(ptf):
    cl = make_cluster(ptf, reuse="on", join_backend="pallas")
    ex = cl.run_workload(skewed(cl.catalog, n_queries=8))
    reg = MetricsRegistry()
    register_summary_counters(reg)
    for e in ex:
        record_executed(reg, e)
    assert reg.as_summary() == workload_summary(ex)


# --------------------------- satellite 1: leftover events after last query

def test_leftover_events_surface_in_summary(ptf):
    """A ``fail_node`` *after* the last query used to leave its recovery
    counters stranded in the pending channel forever; they must now be
    drained into the summary, leaving the channel empty."""
    cl = make_cluster(ptf, replication="hot", telemetry="on")
    ex = cl.run_workload(skewed(cl.catalog), batch_size=6)
    baseline = workload_summary(ex)
    cl.fail_node(0)
    pending = cl.coordinator.events.peek()
    assert pending and "failover_readmits" in pending
    summ = cl.summary(ex)                       # drains the leftovers
    assert cl.coordinator.events.empty()
    assert summ["failover_readmits"] == \
        baseline.get("failover_readmits", 0) + pending["failover_readmits"]
    for k in ("recovery_bytes_from_replica", "recovery_bytes_from_raw",
              "recovery_s"):
        assert k in summ
    # the drain is one-shot: a second summary is back to the baseline
    assert cl.summary(ex) == baseline


def test_events_between_queries_still_drain_into_executed(ptf):
    """The pre-existing path: events posted mid-workload land on the next
    executed query, not in the leftover drain."""
    cl = make_cluster(ptf, replication="hot", telemetry="on")
    queries = skewed(cl.catalog)
    cl.run_workload(queries[:9], batch_size=3)
    cl.fail_node(0)
    assert not cl.coordinator.events.empty()
    more = cl.run_workload(queries[9:], batch_size=3)
    assert cl.coordinator.events.empty()        # drained by execution
    assert sum(e.failover_readmits or 0 for e in more) > 0


# ------------------------------------------------- clock injection sites

def test_result_cache_accepts_clock_objects_and_callables():
    from repro.core.geometry import Box
    now = [0.0]
    rc1 = ResultCache(ttl_s=10.0, clock=lambda: now[0])      # back-compat
    mc = ManualClock()
    rc2 = ResultCache(ttl_s=10.0, clock=mc)                  # Clock object
    key = ResultCache.key_of(Box((0,), (1,)), 1)
    rc1.store(key, 5)
    rc2.store(key, 5)
    now[0] = 11.0
    mc.advance(11.0)
    assert rc1.lookup(key) is None and rc1.expired_drops == 1
    assert rc2.lookup(key) is None and rc2.expired_drops == 1


def test_cluster_coordinator_shares_telemetry_clock(ptf):
    mc = ManualClock(auto_step=0.001)
    cl = make_cluster(ptf, telemetry=Telemetry("on", clock=mc))
    assert cl.coordinator.clock.now() == pytest.approx(mc.now() - 0.001)
    assert cl.telemetry.tracer.clock is mc
