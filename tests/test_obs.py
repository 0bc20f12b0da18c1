"""repro.obs telemetry plane: typed instruments + registry semantics, span
nesting, event log, ring bounding, JSONL export round-trips, the uniform
to_dict/from_dict report surface, disabled-path bit-identity (in-process AND
— with 4 fake devices + forced refits — a full `run_ingest` subprocess under
REPRO_OBS=0 vs on), plus the <5% disabled-overhead pin on the serve hot
path."""
import hashlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test sees an enabled, empty, exporter-free, rule-free plane —
    and leaves the process-global singletons the way it found them."""
    prev_on = obs.set_enabled(True)
    prev_ex = obs.set_exporter(None)
    obs.SLO.set_rules([])
    obs.reset()
    yield
    obs.reset()
    obs.SLO.set_rules([])
    obs.set_exporter(prev_ex)
    obs.set_enabled(prev_on)


def _fresh_pipe(seed=0):
    from repro import api
    return (api.TieringPipeline.from_synthetic(seed=seed, scale="tiny")
            .mine(min_support=1e-3).solve("greedy", budget_frac=0.5))


def _strip_timing(obj):
    """Drop wall-clock-dependent keys so two deterministic runs compare."""
    if isinstance(obj, dict):
        return {k: _strip_timing(v) for k, v in obj.items()
                if "seconds" not in k and k != "ts"}
    if isinstance(obj, list):
        return [_strip_timing(v) for v in obj]
    return obj


# -- Ring ---------------------------------------------------------------------

def test_ring_bounds_and_drop_accounting():
    r = obs.Ring(3)
    for i in range(7):
        r.append(i)
    assert r.to_list() == [4, 5, 6]
    assert len(r) == 3 and r.n_seen == 7 and r.n_dropped == 4
    assert r[0] == 4 and r[-1] == 6 and r[1:] == [5, 6]
    assert bool(r) and list(r) == [4, 5, 6]


def test_ring_unbounded_and_invalid_capacity():
    r = obs.Ring(None)
    r.extend(range(100))
    assert len(r) == 100 and r.n_dropped == 0
    with pytest.raises(ValueError):
        obs.Ring(0)


# -- registry & instruments ---------------------------------------------------

def test_counter_labels_total_and_monotonicity():
    c = obs.counter("t_words", labels=("tier", "shard"))
    c.inc(5, tier="t1", shard=0)
    c.inc(3, tier="t2", shard=1)
    c.inc(2, tier="t1", shard=0)
    assert c.value(tier="t1", shard=0) == 7
    assert c.total() == 10
    assert obs.REGISTRY.total("t_words") == 10
    with pytest.raises(ValueError, match="monotonic"):
        c.inc(-1, tier="t1", shard=0)
    with pytest.raises(ValueError, match="labels"):
        c.inc(1, tier="t1")                     # missing a label


def test_registry_idempotent_and_conflicts():
    a = obs.counter("t_same", labels=("x",))
    assert obs.counter("t_same", labels=("x",)) is a
    with pytest.raises(ValueError, match="already registered"):
        obs.gauge("t_same")                     # kind conflict
    with pytest.raises(ValueError, match="already registered"):
        obs.counter("t_same", labels=("y",))    # label conflict
    obs.histogram("t_h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="conflicting buckets"):
        obs.histogram("t_h", buckets=(1.0, 3.0))


def test_histogram_observe_percentile_snapshot():
    h = obs.histogram("t_lat", buckets=(1.0, 10.0, 100.0))
    h.observe(0.5)
    h.observe_many([5.0, 5.0, 50.0, 500.0])
    snap = h.snapshot()
    assert snap["counts"] == [1, 2, 1, 1]       # last bucket = overflow
    assert snap["count"] == 5 and snap["min"] == 0.5 and snap["max"] == 500.0
    assert snap["sum"] == pytest.approx(560.5)
    assert 1.0 <= h.percentile(50) <= 10.0
    assert h.percentile(100) == 500.0           # overflow lands on max
    assert obs.histogram("t_empty").percentile(50) != \
        obs.histogram("t_empty").percentile(50)  # NaN on empty


def test_registry_reset_keeps_instrument_identity():
    c = obs.counter("t_keep")
    c.inc(4)
    obs.reset()
    assert c.value() == 0
    assert obs.counter("t_keep") is c
    c.inc(1)                                    # held references still work
    assert obs.REGISTRY.total("t_keep") == 1


# -- spans & events -----------------------------------------------------------

def test_span_nesting_parent_depth_and_dict():
    with obs.span("outer", n=2) as a:
        with obs.span("inner") as b:
            b.set(hits=3)
        assert b.parent == a.id and b.depth == a.depth + 1
    recs = obs.SPANS.to_list()                  # finished spans, as dicts
    assert [r["name"] for r in recs] == ["inner", "outer"]  # exit order
    d = recs[0]
    assert d["name"] == "inner" and d["hits"] == 3
    assert d["wall_ms"] >= 0.0 and d["parent"] == a.id
    assert d["depth"] == 1 and recs[1]["depth"] == 0
    assert {"id", "parent", "depth", "t0_s", "wall_ms", "sync_ms"} <= set(d)
    assert obs.SPANS.of_name("inner") == [d]
    assert obs.SPANS.children(a.id) == [d]


def test_span_sync_passes_through_host_values():
    with obs.span("s") as sp:
        assert sp.sync([1, 2, 3]) == [1, 2, 3]  # non-JAX values untouched
        arr = sp.sync(np.arange(3))
        np.testing.assert_array_equal(arr, [0, 1, 2])


def test_span_sync_propagates_device_errors(monkeypatch):
    """Only values holding no JAX array skip the wait; an error raised
    while waiting on the device reaches the caller."""
    import jax
    import jax.numpy as jnp

    def fail(value):
        raise RuntimeError("device fault")

    monkeypatch.setattr(jax, "block_until_ready", fail)
    with obs.span("s") as sp:
        assert sp.sync({"host": np.arange(2)})["host"].tolist() == [0, 1]
        with pytest.raises(RuntimeError, match="device fault"):
            sp.sync({"dev": jnp.arange(2)})


def test_event_log_and_cursors():
    obs.event("alpha", x=1)
    seq = obs.EVENTS.seq
    obs.event("beta", y=2)
    since = obs.EVENTS.since(seq)
    assert [e["kind"] for e in since] == ["beta"]
    assert since[0]["y"] == 2 and "t_s" in since[0]
    assert [e["kind"] for e in obs.EVENTS.of_kind("alpha")] == ["alpha"]


def test_disabled_plane_is_noop():
    obs.set_enabled(False)
    sp = obs.span("anything", n=1)
    assert sp is obs.NULL_SPAN                  # shared singleton: no alloc
    with sp as s:
        s.set(x=1)
        assert s.sync("v") == "v"
    assert obs.event("nothing") is None
    c = obs.counter("t_off")
    c.inc(5)
    g = obs.gauge("t_off_g")
    g.set(3.0)
    h = obs.histogram("t_off_h")
    h.observe(1.0)
    assert c.total() == 0 and g.value() is None and h.snapshot()["count"] == 0
    assert len(obs.SPANS.ring) == 0 and len(obs.EVENTS) == 0
    # ... but a detached always=True instrument records regardless
    d = obs.Histogram("t_detached", always=True, buckets=(1.0, 2.0))
    d.observe(1.5)
    assert d.snapshot()["count"] == 1


# -- render -------------------------------------------------------------------

def test_render_line_formatting():
    from repro.obs.render import render_line
    line = render_line("tag", [("@head", "3 windows"), ("cov", 0.5),
                               ("ok", True), ("bad", False),
                               ("skip", None), ("xs", [1, 2])])
    assert line == "tag  3 windows  cov=0.500  ok=ok  bad=FAIL  xs=[1,2]"


# -- export -------------------------------------------------------------------

def test_jsonl_exporter_round_trip(tmp_path):
    ex = obs.JsonlExporter(tmp_path, run="r1")
    ex.export({"window": 0, "v": np.int64(3), "a": np.arange(2)})
    ex.export({"window": 1, "v": 4, "a": []})
    snaps = obs.read_jsonl(ex.path)
    assert [s["window"] for s in snaps] == [0, 1]
    assert snaps[0]["v"] == 3 and snaps[0]["a"] == [0, 1]
    assert obs.load_dir(tmp_path) == {"r1": snaps}
    # a named run restarts its file on re-construction
    obs.JsonlExporter(tmp_path, run="r1").export({"window": 9})
    assert [s["window"] for s in obs.read_jsonl(ex.path)] == [9]


def test_export_window_cursors_and_gating(tmp_path):
    assert obs.export_window(0) is None         # no exporter installed: no-op
    obs.set_exporter(obs.JsonlExporter(tmp_path, run="w"))
    with obs.span("s1"):
        pass
    obs.event("e1")
    snap0 = obs.export_window(0)
    with obs.span("s2"):
        pass
    snap1 = obs.export_window(1, extra_key="x")
    assert [s["name"] for s in snap0["spans"]] == ["s1"]
    assert [s["name"] for s in snap1["spans"]] == ["s2"]   # cursor advanced
    assert [e["kind"] for e in snap0["events"]] == ["e1"]
    assert snap1["events"] == [] and snap1["extra_key"] == "x"
    snaps = obs.read_jsonl(obs.get_exporter().path)
    assert len(snaps) == 2
    for s in snaps:
        assert {"window", "ts", "metrics", "spans", "events"} <= set(s)
    obs.set_enabled(False)
    assert obs.export_window(2) is None         # disabled: no write
    assert len(obs.read_jsonl(obs.get_exporter().path)) == 2


def test_launch_obs_check_gate():
    from repro.launch.obs import check
    good = {"r": [{"window": 0, "ts": 0.0, "events": [], "spans": [],
                   "metrics": {"m": {"type": "counter", "series": [
                       {"labels": {}, "value": 3}]}}}]}
    assert check(good, ["m"]) == 0
    assert check(good, ["missing_metric"]) == 1
    assert check({}, []) == 1                   # no runs at all
    assert check({"r": [{"window": 0}]}, []) == 1   # missing required keys


def test_launch_obs_check_max_dropped_frac():
    from repro.launch.obs import check

    def run(spans_seen, spans_dropped):
        return {"r": [{"window": 0, "ts": 0.0, "events": [], "spans": [],
                       "metrics": {},
                       "rings": {"spans": {"n_seen": spans_seen,
                                           "n_dropped": spans_dropped},
                                 "events": {"n_seen": 0, "n_dropped": 0}}}]}

    assert check(run(100, 10), [], max_dropped_frac=0.5) == 0
    assert check(run(100, 60), [], max_dropped_frac=0.5) == 1
    assert check(run(0, 0), [], max_dropped_frac=0.0) == 0
    # a snapshot without the rings block can't prove retention: fail
    legacy = {"r": [{"window": 0, "ts": 0.0, "events": [], "spans": [],
                     "metrics": {}}]}
    assert check(legacy, [], max_dropped_frac=0.5) == 1
    assert check(legacy, []) == 0               # ... unless the flag is off


def test_snapshot_rings_and_empty_window(tmp_path):
    obs.set_exporter(obs.JsonlExporter(tmp_path, run="rings"))
    empty = obs.export_window(0)                # no activity at all: valid
    assert empty["spans"] == [] and empty["events"] == []
    assert empty["slo"] == {}                   # no rules installed
    assert empty["rings"]["spans"] == {"n_seen": 0, "n_dropped": 0}
    from repro.obs.events import DEFAULT_EVENT_CAPACITY
    n = DEFAULT_EVENT_CAPACITY + 50
    for i in range(n):
        obs.event("flood", i=i)
    dropped = obs.export_window(1)
    assert dropped["rings"]["events"] == {"n_seen": n, "n_dropped": 50}
    # the payload round-trips through JSONL read/load_dir intact
    snaps = obs.read_jsonl(obs.get_exporter().path)
    assert obs.load_dir(tmp_path) == {"rings": snaps}
    assert [s["window"] for s in snaps] == [0, 1]
    assert snaps[1]["rings"]["events"]["n_dropped"] == 50
    assert snaps[0]["rings"] == empty["rings"]
    for s in snaps:
        assert {"window", "ts", "metrics", "spans", "events",
                "slo", "rings"} <= set(s)


# -- uniform report dict surface ----------------------------------------------

def test_serve_stats_round_trip():
    from repro.serve.engine import ServeStats
    s = ServeStats(n_queries=10, n_tier1=6, tier1_words=120, tier2_words=400,
                   full_words_per_query=100)
    d = s.to_dict()
    assert d["tier1_fraction"] == pytest.approx(0.6)
    assert 0.0 < d["cost_saving"] <= 1.0        # derived keys exported...
    assert ServeStats.from_dict(d) == s         # ...and ignored on the way in


def test_stream_and_ingest_report_round_trips():
    from repro import ingest, stream
    from repro.ingest.controller import IngestReport, IngestWindowReport
    from repro.stream.controller import StreamReport, WindowReport
    pipe = _fresh_pipe()
    rep = stream.run_stream(pipe, scenario="rotate", n_windows=2,
                            queries_per_window=64, seed=0)
    rt = StreamReport.from_dict(rep.to_dict())
    assert rt.to_dict() == rep.to_dict()
    assert isinstance(rt.windows[0], WindowReport)
    assert rt.summary() == rep.summary()
    irep = ingest.run_ingest(_fresh_pipe(), scenario="rotate", n_windows=2,
                             queries_per_window=64, seed=0,
                             arrivals_per_window=8.0)
    irt = IngestReport.from_dict(irep.to_dict())
    assert irt.to_dict() == irep.to_dict()
    assert isinstance(irt.windows[0], IngestWindowReport)
    assert irt.windows[0].line() == irep.windows[0].line()
    assert irt.summary() == irep.summary()


def test_loadgen_hist_and_round_trip_and_switch_independence():
    from repro import cluster
    from repro.cluster.loadgen import LoadgenReport
    pipe = _fresh_pipe()
    fleet = pipe.deploy_cluster(n_shards=2, t1_replicas=2)
    plan = cluster.ClusterPlan.of_cluster(fleet)
    elig = fleet.classify(pipe.log.queries[:256])

    def run():
        return cluster.run_loadgen(plan, elig, n_queries=1000, seed=0)

    rep = run()
    hist = rep.latency_hist
    assert sum(hist["counts"]) == hist["count"] == 1000
    assert hist["min"] <= rep.p50_ms <= rep.p95_ms <= hist["max"]
    assert LoadgenReport.from_dict(rep.to_dict()).to_dict() == rep.to_dict()
    # the histogram is detached (always=True): REPRO_OBS must not change it
    obs.set_enabled(False)
    assert run().to_dict() == rep.to_dict()


# -- BatchTrace bounding ------------------------------------------------------

def test_cluster_trace_ring_bounding():
    from repro.cluster.router import DEFAULT_TRACE_CAPACITY
    pipe = _fresh_pipe()
    fleet = pipe.deploy_cluster(n_shards=2, trace_capacity=4)
    batch = pipe.log.queries[:16]
    for _ in range(6):
        fleet.serve(batch)
    assert len(fleet.trace) == 4                # last 4 batches survive
    assert fleet.trace.n_seen == 6 and fleet.trace.n_dropped == 2
    assert fleet.consistency_ok()               # checks run on the window
    unbounded = pipe.deploy_cluster(n_shards=2, trace_capacity=None)
    for _ in range(3):
        unbounded.serve(batch)
    assert len(unbounded.trace) == 3 and unbounded.trace.n_dropped == 0
    default = pipe.deploy_cluster(n_shards=2)
    default.serve(batch)
    assert default.trace.capacity == DEFAULT_TRACE_CAPACITY


# -- instrumented call sites --------------------------------------------------

def test_engine_serve_spans_metrics_and_bit_identity():
    pipe = _fresh_pipe()
    queries = pipe.log.queries[:64]
    engine = pipe.deploy()
    on = engine.serve(queries)
    spans = {s["name"]: s for s in obs.SPANS.to_list()}
    assert {"serve", "classify", "merge"} <= set(spans)
    assert "t1_match" in spans or "t2_match" in spans
    for name in ("classify", "merge"):
        assert spans[name]["parent"] == spans["serve"]["id"]
    assert obs.REGISTRY.total("serve_queries_total") == 64
    assert obs.REGISTRY.total("serve_words_total") > 0
    # identical serve with the plane off — results and stats bit-equal
    obs.set_enabled(False)
    engine_off = pipe.deploy()
    off = engine_off.serve(queries)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)
    assert engine.stats.to_dict() == engine_off.stats.to_dict()
    assert obs.REGISTRY.total("serve_queries_total") == 64   # no new counts


def test_cluster_serve_per_shard_counters_and_events():
    pipe = _fresh_pipe()
    fleet = pipe.deploy_cluster(n_shards=2, t1_replicas=2)
    fleet.serve(pipe.log.queries[:64])
    c = obs.REGISTRY.get("cluster_words_total")
    shards = {s["labels"]["shard"] for s in c.to_dict()["series"]}
    assert shards == {"0", "1"}
    from repro.core import SOLVERS
    from repro.core.tiering import ClauseTiering
    r2 = SOLVERS["greedy"](pipe.problem, int(pipe.data.n_docs * 0.25))
    fleet.swap_tiering(ClauseTiering.from_selection(pipe.data, r2.selected),
                       immediate=True)
    assert obs.EVENTS.of_kind("rollout_begin")
    assert obs.EVENTS.of_kind("rollout_done")
    assert obs.EVENTS.of_kind("replica_swap")   # per-replica commits


def test_run_stream_bit_identical_with_plane_off():
    kw = dict(scenario="rotate", n_windows=3, queries_per_window=96, seed=0)
    from repro import stream
    on = stream.run_stream(_fresh_pipe(), **kw)
    assert obs.REGISTRY.total("serve_queries_total") > 0
    assert len(obs.SPANS.ring) > 0
    obs.set_enabled(False)
    off = stream.run_stream(_fresh_pipe(), **kw)
    assert _strip_timing(on.to_dict()) == _strip_timing(off.to_dict())


def test_solver_trace_emits_solve_event():
    _fresh_pipe()
    ev = obs.EVENTS.of_kind("solve_done")
    assert ev and ev[-1]["solver"] == "greedy"
    assert ev[-1]["n_selections"] > 0 and ev[-1]["f_final"] > 0
    assert obs.REGISTRY.total("solver_selections_total") > 0


# -- kernel profiler (repro.obs.profile) --------------------------------------

def test_kernel_profiler_counters_and_measuring():
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 2 ** 32, (64, 8), dtype=np.uint32))
    mask = jnp.asarray(rng.integers(0, 2 ** 32, 8, dtype=np.uint32))
    on = np.asarray(ops.coverage_gain(a, mask))
    assert obs.REGISTRY.total("kernel_words_scanned_total") == 64 * 8
    assert obs.REGISTRY.total("kernel_bytes_moved_total") > 0
    assert obs.PROFILER.summary() == []         # not measuring: no sync rows
    with obs.PROFILER.measuring():
        ops.coverage_gain(a, mask)
        ops.coverage_gain(a, mask)
    rows = obs.PROFILER.summary()
    assert [(r["op"], r["path"], r["calls"]) for r in rows] == \
        [("coverage_gain", "xla", 2)]
    r = rows[0]
    assert r["words_scanned"] == 2 * 64 * 8
    assert r["achieved_gbps"] > 0.0 and r["roofline_frac"] > 0.0
    assert r["roofline_frac"] == pytest.approx(
        r["achieved_gbps"] / (obs.HBM_BW / 1e9), abs=1e-6)  # 6-dp rounding
    obs.reset()
    assert obs.PROFILER.summary() == []         # reset drops the aggregation
    # disabled: dispatch records nothing and the result stays bit-identical
    obs.set_enabled(False)
    off = np.asarray(ops.coverage_gain(a, mask))
    np.testing.assert_array_equal(on, off)
    assert obs.REGISTRY.total("kernel_words_scanned_total") == 0
    with obs.PROFILER.measuring():
        ops.coverage_gain(a, mask)
    assert obs.PROFILER.summary() == []


def test_kernel_profiler_labels_every_public_op():
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(0, 2 ** 32, (32, 4), dtype=np.uint32))
    x = jnp.asarray(rng.standard_normal((4 * 32, 2)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2 ** 32, 4, dtype=np.uint32))
    q = jnp.asarray(rng.integers(0, 2 ** 32, (8, 4), dtype=np.uint32))
    ids = jnp.asarray(rng.integers(0, 50, (16, 6)), jnp.int32)
    ops.bit_matvec(a, x)
    ops.coverage_gain(a, mask)
    ops.clause_match(q, a[:3])
    ops.partition_gain(a, mask, (0, 2, 4))
    ops.sparse_gain(ids, jnp.zeros(50, bool))
    c = obs.REGISTRY.get("kernel_words_scanned_total")
    by_op = {s["labels"]["op"]: s["value"] for s in c.to_dict()["series"]}
    assert set(by_op) == {"bit_matvec", "coverage_gain", "clause_match",
                          "partition_gain", "sparse_gain"}
    assert by_op["bit_matvec"] == 32 * 4
    assert by_op["partition_gain"] == 32 * 4 + 4
    # the empty-operand clause_match early return never dispatches
    before = by_op["clause_match"]
    ops.clause_match(q, a[:0])
    c2 = {s["labels"]["op"]: s["value"]
          for s in c.to_dict()["series"]}["clause_match"]
    assert c2 == before


# -- SLO engine over live windows ---------------------------------------------

def test_slo_disabled_is_complete_noop():
    obs.SLO.set_rules(obs.default_slo_rules())
    obs.set_enabled(False)
    assert obs.SLO.evaluate(0) == {}
    assert obs.SLO.breached() == []
    assert obs.REGISTRY.total("slo_breaches_total") == 0


def test_slo_breach_and_recover_deterministic(tmp_path):
    """A seeded loadgen overload window against a tightened p95 rule must
    produce exactly slo_breach -> slo_recovered, in the JSONL payload, the
    EventLog, the breach counter, and the dashboard segment."""
    from repro import cluster
    pipe = _fresh_pipe()
    fleet = pipe.deploy_cluster(n_shards=2, t1_replicas=2)
    plan = cluster.ClusterPlan.of_cluster(fleet)
    elig = fleet.classify(pipe.log.queries[:256])
    obs.set_exporter(obs.JsonlExporter(tmp_path, run="slo"))
    obs.SLO.set_rules([obs.SLORule(
        "p95_tight", "p95:loadgen_latency_ms", max=1.0,
        fast_windows=1, slow_windows=4, slow_burn=0.25, clear_windows=2)])

    def window(i, qps):
        cluster.run_loadgen(plan, elig, rate_qps=qps, n_queries=400, seed=i)
        return obs.export_window(i)

    s0 = window(0, 1e6)       # open-loop overload: queueing blows the tail
    assert s0["slo"]["rules"]["p95_tight"]["bad"] is True
    assert s0["slo"]["breached"] == ["p95_tight"]
    assert [e["rule"] for e in s0["events"]
            if e["kind"] == "slo_breach"] == ["p95_tight"]
    assert "slo=BREACH(p95_tight)" in obs.dashboard()
    assert obs.REGISTRY.total("slo_breaches_total") == 1

    s1 = window(1, 50.0)      # light load: good, but hysteresis holds
    assert s1["slo"]["rules"]["p95_tight"]["bad"] is False
    assert s1["slo"]["breached"] == ["p95_tight"]
    s2 = window(2, 50.0)      # second consecutive good window: recovered
    assert s2["slo"]["breached"] == []
    assert [e["rule"] for e in s2["events"]
            if e["kind"] == "slo_recovered"] == ["p95_tight"]
    assert "slo=ok(1)" in obs.dashboard()
    assert obs.REGISTRY.total("slo_breaches_total") == 1   # transitions only

    snaps = obs.read_jsonl(obs.get_exporter().path)
    kinds = [(s["window"], e["kind"]) for s in snaps for e in s["events"]
             if e["kind"].startswith("slo_")]
    assert kinds == [(0, "slo_breach"), (2, "slo_recovered")]
    # primed series: the counter exports even for never-breached rules
    series = snaps[-1]["metrics"]["slo_breaches_total"]["series"]
    assert {s["labels"]["rule"]: s["value"]
            for s in series} == {"p95_tight": 1}


# -- disabled-path overhead pin ----------------------------------------------

def test_disabled_overhead_under_5pct():
    """serve/engine.py's exact hot-path wrapping (span + sync + counter inc)
    must cost <5% over bare `match_batch` when the plane is off."""
    import jax.numpy as jnp
    from repro.serve import matching
    rng = np.random.default_rng(0)
    postings = jnp.asarray(
        rng.integers(0, 2 ** 32, (1024, 128), dtype=np.uint32))
    toks = jnp.asarray(rng.integers(0, 1024 * 32, (128, 8)), np.int32)
    ctr = obs.counter("t_overhead")

    def plain():
        np.asarray(matching.match_batch(postings, toks))

    def wrapped():
        with obs.span("t1_match", n=128) as sp:
            sp.sync(matching.match_batch(postings, toks))
        ctr.inc(128)

    def best(fn, iters=20, reps=5):
        fn()                                    # warm/compile
        out = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            out.append((time.perf_counter() - t0) / iters)
        return min(out)

    obs.set_enabled(False)
    t_plain = best(plain)
    t_obs = best(wrapped)
    assert t_obs <= t_plain * 1.05 + 5e-5, \
        f"disabled-path overhead: plain={t_plain * 1e6:.1f}us " \
        f"obs={t_obs * 1e6:.1f}us (+{(t_obs / t_plain - 1) * 100:.1f}%)"
    assert ctr.total() == 0                     # it really was off


# -- acceptance: forced-4-device ingest run, obs on vs REPRO_OBS=0 ------------

ACCEPT_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import hashlib, json
import jax
import numpy as np
from repro import api, distributed as D, ingest, obs, stream

assert len(jax.devices()) == 4
out_dir = sys.argv[1]
if obs.enabled():
    obs.set_exporter(obs.JsonlExporter(out_dir, run="accept"))

pipe = (api.TieringPipeline.from_synthetic(seed=0, scale="tiny")
        .mine(min_support=1e-3).solve("greedy", budget_frac=0.5))
fleet = pipe.deploy_cluster(n_shards=2, t1_replicas=2, t2_replicas=2)
# coverage_drop=-1 forces the drift trigger every eligible window, so the
# 3-window run deterministically produces drift/refit/swap events
report = ingest.run_ingest(
    pipe, scenario="rotate", n_windows=3, queries_per_window=192, seed=0,
    arrivals_per_window=24.0, engine=fleet,
    detector=stream.DriftDetector(coverage_drop=-1.0, warmup_windows=0,
                                  min_windows_between=0))
assert report.n_refits >= 1 and report.n_ingested >= 1


def strip(o):
    if isinstance(o, dict):
        return {k: strip(v) for k, v in o.items() if "seconds" not in k}
    if isinstance(o, list):
        return [strip(v) for v in o]
    return o


queries = pipe.log.queries[:64]
digest = {"report": strip(report.to_dict()),
          "stats": strip(fleet.stats.to_dict()),
          "trace": [(t.psi_generation, t.n_tier1, t.n_tier2)
                    for t in fleet.trace],
          "matches": [np.asarray(m).tolist() for m in fleet.serve(queries)]}
host2 = pipe.deploy_cluster(n_shards=2, t1_replicas=2, t2_replicas=2)
a = host2.serve(queries)
mesh_fleet = pipe.deploy_cluster(n_shards=2, t1_replicas=2, t2_replicas=2)
with D.use_mesh(D.shard_mesh()):
    b = mesh_fleet.serve(queries)
for x, y in zip(a, b):
    np.testing.assert_array_equal(x, y)
digest["mesh"] = [np.asarray(m).tolist() for m in b]

if obs.enabled():
    snaps = obs.read_jsonl(obs.get_exporter().path)
    assert len(snaps) == 3, len(snaps)
    for s in snaps:
        assert {"window", "ts", "metrics", "spans", "events"} <= set(s)
    words = snaps[-1]["metrics"]["cluster_words_total"]["series"]
    combos = {(s["labels"]["tier"], s["labels"]["shard"]) for s in words}
    assert {("t1", "0"), ("t1", "1"), ("t2", "0"),
            ("t2", "1")} <= combos, combos
    spans = [sp for s in snaps for sp in s["spans"]]
    serves = [sp for sp in spans if sp["name"] == "serve"]
    nested = False
    for sv in serves:
        kids = {sp["name"] for sp in spans if sp["parent"] == sv["id"]}
        if {"classify", "t1_match", "merge"} <= kids:
            assert sv["wall_ms"] >= 0.0 and sv["depth"] == 0
            nested = True
    assert nested, "no serve span nesting classify/t1_match/merge"
    kinds = {e["kind"] for s in snaps for e in s["events"]}
    assert {"drift_detected", "refit", "corpus_swap"} <= kinds, kinds
    mesh_spans = obs.SPANS.of_name("mesh_fused")
    assert mesh_spans and mesh_spans[-1]["sync_ms"] >= 0.0
    print("OBS-ACCEPT-OK")

blob = json.dumps(digest, sort_keys=True, default=float)
print("DIGEST=" + hashlib.sha256(blob.encode()).hexdigest())
print("INGEST-OBS-DONE")
"""


def _run_accept(tmp_path, obs_env):
    env = {"PYTHONPATH": "src", "PATH": os.environ.get(
        "PATH", "/usr/bin:/bin"), "HOME": os.environ.get("HOME", "/root")}
    if obs_env is not None:
        env["REPRO_OBS"] = obs_env
    out = subprocess.run(
        [sys.executable, "-c", ACCEPT_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."), timeout=900)
    assert "INGEST-OBS-DONE" in out.stdout, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    digest = [ln for ln in out.stdout.splitlines()
              if ln.startswith("DIGEST=")][0]
    return out.stdout, digest


def test_ingest_obs_acceptance_4dev_and_off_bit_identity(tmp_path):
    stdout_on, digest_on = _run_accept(tmp_path, None)
    assert "OBS-ACCEPT-OK" in stdout_on
    assert os.path.exists(tmp_path / "accept.jsonl")
    _, digest_off = _run_accept(tmp_path, "0")
    assert digest_on == digest_off              # REPRO_OBS=0: bit-identical
