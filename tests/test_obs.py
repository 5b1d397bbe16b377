"""Unified observability: metrics registry semantics, span tracer
output (Chrome trace-event JSON), the disabled-path no-op guarantees,
PhaseTimers-as-span-reducer behavior, and router/fleet stats + SLO
accounting under ensemble fan-out.

The load-bearing properties: (1) with observability disabled, every
instrumentation point is a no-op that cannot perturb the computation;
(2) enabled, the emitted artifacts are schema-valid and internally
consistent (histogram counts match completions, SLO ok+miss ==
completed, thread tracks are correctly named).
"""

import gc
import json
import os
import tempfile
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import (LATENCY_MS_EDGES, Counter, Gauge, Histogram,
                               MetricsLogger, MetricsRegistry)
from repro.obs.trace import _NULL_SPAN, SpanTracer
from repro.perf import PhaseTimers


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()


# -- metrics primitives -------------------------------------------------------

def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_and_set_max():
    g = Gauge()
    g.set(3)
    g.set_max(2)
    assert g.value == 3
    g.set_max(7)
    assert g.value == 7


def test_histogram_buckets_and_percentile():
    h = Histogram(edges=(1.0, 10.0, 100.0))
    for v in (0.5, 0.7, 5.0, 50.0, 500.0):
        h.observe(v)
    assert h.count == 5
    assert h.bucket_counts == [2, 1, 1, 1]  # (<=1, <=10, <=100, +inf]
    # p50 lands in the second bucket (cumulative 2 < 2.5 <= 3)
    p50 = h.percentile(50)
    assert 1.0 <= p50 <= 10.0
    assert Histogram(edges=(1.0,)).percentile(50) is None


def test_hist_percentile_interpolates_within_bucket():
    from repro.obs.metrics import hist_percentile

    h = Histogram(edges=(1.0, 10.0, 100.0))
    for v in (0.5, 0.7, 5.0, 50.0, 500.0):
        h.observe(v)
    # rank 2.5 of 5 lands mid-second-bucket: 1 + 0.5/1 * (10-1) = 5.5,
    # never an edge value.
    assert h.percentile(50) == pytest.approx(5.5)
    # a rank in the unbounded overflow bucket clamps to the last finite
    # edge (a lower bound) instead of fabricating an upper one.
    assert h.percentile(99) == pytest.approx(100.0)
    # degenerate inputs resolve, not crash
    assert hist_percentile([], [], 50) is None
    assert hist_percentile([1.0], [0, 0], 50) is None
    assert hist_percentile([4.0], [2, 0], 50) == pytest.approx(2.0)


def test_histogram_rejects_bad_edges():
    with pytest.raises(ValueError):
        Histogram(edges=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        Histogram(edges=())


def test_registry_identity_and_conflicts():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    assert r.counter("a", k="1") is not r.counter("a", k="2")
    with pytest.raises(ValueError):
        r.gauge("a")  # same name, different type
    r.histogram("h", edges=(1.0, 2.0))
    with pytest.raises(ValueError):
        r.histogram("h", edges=(1.0, 3.0))  # same name, different edges
    assert r.get("a") is r.counter("a")
    assert r.get("nope") is None


def test_registry_snapshot_schema():
    r = MetricsRegistry()
    r.counter("c", x="1").inc(2)
    r.gauge("g").set(1.5)
    r.histogram("h", edges=(1.0, 2.0)).observe(1.5)
    snap = r.snapshot()
    assert [m["name"] for m in snap] == ["c", "g", "h"]
    by_name = {m["name"]: m for m in snap}
    assert by_name["c"] == {"name": "c", "type": "counter",
                            "labels": {"x": "1"}, "value": 2}
    assert by_name["g"]["value"] == 1.5
    h = by_name["h"]
    assert h["count"] == 1 and len(h["bucket_counts"]) == len(h["le"]) + 1


def test_metrics_logger_jsonl(tmp_path):
    r = MetricsRegistry()
    r.counter("c").inc()
    path = str(tmp_path / "m.jsonl")
    log = MetricsLogger(r, path, proc="w0")
    log.flush()
    r.counter("c").inc()
    log.close()  # final snapshot
    lines = [json.loads(s) for s in open(path).read().splitlines()]
    assert len(lines) == 2
    for i, line in enumerate(lines):
        assert set(line) == {"ts", "proc", "seq", "metrics"}
        assert line["proc"] == "w0"
        assert line["seq"] == i  # monotone per-logger sequence
    assert lines[0]["metrics"][0]["value"] == 1
    assert lines[1]["metrics"][0]["value"] == 2


def test_metrics_logger_proc_default_and_env(tmp_path, monkeypatch):
    r = MetricsRegistry()
    monkeypatch.delenv("REPRO_METRICS_PROC", raising=False)
    log = MetricsLogger(r, str(tmp_path / "a.jsonl"))
    assert log.proc == f"pid{os.getpid()}"
    log.close()
    monkeypatch.setenv("REPRO_METRICS_PROC", "shard3")
    log = MetricsLogger(r, str(tmp_path / "b.jsonl"))
    assert log.proc == "shard3"
    log.close()


def test_metrics_logger_rate_limit(tmp_path):
    r = MetricsRegistry()
    path = str(tmp_path / "m.jsonl")
    log = MetricsLogger(r, path, min_interval_s=3600)
    log.flush(force=False)
    log.flush(force=False)  # rate-limited away
    log.flush(force=True)
    log.close()
    lines = open(path).read().splitlines()
    assert len(lines) == 3  # 1 + forced + close
    # seq numbers every WRITTEN line contiguously (suppressed flushes
    # must not burn sequence numbers — the merge sort key relies on it)
    assert [json.loads(s)["seq"] for s in lines] == [0, 1, 2]
    stats = log.stats()
    assert stats["flushes"] == 3
    assert stats["suppressed"] == 1
    assert stats["dropped"] == 0
    log.flush()  # after close: data that never reached the file
    assert log.stats()["dropped"] == 1


# -- span tracer --------------------------------------------------------------

@pytest.fixture
def no_auto_gc():
    """Automatic collections off: a recording tracer adds a
    ``python.gc`` span for each, which exact event counts would see."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


def test_disabled_tracer_is_noop_singleton():
    tr = SpanTracer()
    assert tr.span("x") is _NULL_SPAN
    assert tr.span("y", cat="c", block=1) is _NULL_SPAN
    tr.async_begin("a", 1)
    tr.async_end("a", 1)
    assert tr.events() == []


def test_tracer_records_complete_events(tmp_path, no_auto_gc):
    tr = SpanTracer()
    tr.start()
    with tr.span("work", cat="test", block=3):
        pass
    evs = tr.events()
    kinds = [e["ph"] for e in evs]
    assert kinds == ["M", "X"]  # thread metadata precedes the first span
    x = evs[1]
    assert x["name"] == "work" and x["cat"] == "test"
    assert x["args"] == {"block": 3}
    assert x["dur"] >= 0
    path = str(tmp_path / "t.json")
    tr.save(path)
    doc = json.load(open(path))
    assert doc["traceEvents"] == evs
    assert doc["displayTimeUnit"] == "ms"


def test_tracer_async_pairing():
    tr = SpanTracer()
    tr.start()
    tr.async_begin("req", 7, cat="serve", bucket=32)
    tr.async_end("req", 7, cat="serve")
    b, e = [ev for ev in tr.events() if ev["ph"] in "be"]
    assert (b["ph"], e["ph"]) == ("b", "e")
    assert b["id"] == e["id"] == "7"
    assert b["cat"] == e["cat"] == "serve"


def test_tracer_thread_tracks():
    tr = SpanTracer()
    tr.start()
    def work():
        with tr.span("child"):
            pass
    t = threading.Thread(target=work, name="worker-thread")
    t.start()
    t.join()
    with tr.span("main"):
        pass
    meta = {e["tid"]: e["args"]["name"] for e in tr.events()
            if e["ph"] == "M"}
    by_span = {e["name"]: meta[e["tid"]] for e in tr.events()
               if e["ph"] == "X"}
    assert by_span["child"] == "worker-thread"
    assert by_span["main"] == threading.current_thread().name


def test_tracer_drops_past_capacity(no_auto_gc):
    tr = SpanTracer(max_events=3)
    tr.start()
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 3
    assert tr.dropped == 10 - 2  # metadata event consumed one slot


# -- PhaseTimers as a span reducer --------------------------------------------

def test_phase_timers_reduce_spans():
    t = PhaseTimers()
    with t.phase("a"):
        pass
    with t.phase("b"):
        pass
    with t.phase("a"):
        pass
    assert t.counts == {"a": 2, "b": 1}
    assert set(t.totals) == {"a", "b"}
    assert t.total == pytest.approx(sum(t.totals.values()))


def test_phase_timers_reject_nesting():
    t = PhaseTimers()
    with pytest.raises(RuntimeError, match="nested"):
        with t.phase("outer"):
            with t.phase("inner"):
                pass
    # the failed inner entry must not wedge the timer
    with t.phase("after"):
        pass
    assert t.counts["after"] == 1


def test_phase_timers_forward_to_tracer(no_auto_gc):
    tr = obs.enable_tracing()
    t = PhaseTimers()
    with t.phase("sweep"):
        pass
    names = [e["name"] for e in tr.events() if e["ph"] == "X"]
    assert names == ["sweep"]


# -- global setup / disabled path ---------------------------------------------

def test_setup_and_finalize(tmp_path):
    trace_path = str(tmp_path / "t.json")
    metrics_path = str(tmp_path / "m.jsonl")
    obs.setup(trace=trace_path, metrics_path=metrics_path)
    assert obs.metrics_on()
    obs.metrics().counter("x").inc()
    with obs.tracer().span("s"):
        pass
    obs.finalize()
    assert not obs.metrics_on()
    assert not obs.tracer().enabled
    doc = json.load(open(trace_path))
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
    lines = open(metrics_path).read().splitlines()
    assert lines and json.loads(lines[-1])["metrics"][0]["value"] == 1


def test_finalize_returns_sink_summary(tmp_path):
    trace_path = str(tmp_path / "t.json")
    metrics_path = str(tmp_path / "m.jsonl")
    obs.setup(trace=trace_path, metrics_path=metrics_path)
    with obs.tracer().span("s"):
        pass
    obs.flush_metrics(force=True)
    out = obs.finalize()
    assert out["trace"]["path"] == trace_path
    assert out["trace"]["events"] >= 1
    assert out["trace"]["dropped_events"] == 0
    assert out["metrics"]["path"] == metrics_path
    assert out["metrics"]["flushes"] == 2  # explicit + close
    assert out["metrics"]["dropped"] == 0
    assert obs.finalize() == {}  # idempotent: sinks already detached


def test_finalize_surfaces_trace_drops(tmp_path):
    """A truncated trace must be visible in the final metrics snapshot
    (obs.trace_dropped_events), not just in the trace file."""
    trace_path = str(tmp_path / "t.json")
    metrics_path = str(tmp_path / "m.jsonl")
    obs.setup(trace=trace_path, metrics_path=metrics_path)
    old_cap = obs.tracer().max_events
    obs.tracer().max_events = 2
    try:
        for i in range(6):
            with obs.tracer().span(f"s{i}"):
                pass
        out = obs.finalize()
    finally:
        obs.tracer().max_events = old_cap
    assert out["trace"]["dropped_events"] > 0
    doc = json.load(open(trace_path))
    assert doc["otherData"]["dropped_events"] == \
        out["trace"]["dropped_events"]
    last = json.loads(open(metrics_path).read().splitlines()[-1])
    gauges = {m["name"]: m["value"] for m in last["metrics"]}
    assert gauges["obs.trace_dropped_events"] == \
        out["trace"]["dropped_events"]


def test_disabled_by_default():
    assert not obs.metrics_on()
    assert obs.tracer().span("anything") is _NULL_SPAN
    obs.flush_metrics()  # no sink: must be a silent no-op
    # counters stay always-legal even without a sink
    obs.metrics().counter("c").inc()


def test_setup_from_env(tmp_path, monkeypatch):
    trace_path = str(tmp_path / "t.json")
    monkeypatch.setenv("REPRO_TRACE", trace_path)
    monkeypatch.delenv("REPRO_METRICS", raising=False)
    obs.setup_from_env()
    assert obs.tracer().enabled
    assert not obs.metrics_on()
    obs.finalize()
    assert os.path.exists(trace_path)


# -- serve-path stats: router/fleet under ensemble fan-out --------------------

@pytest.fixture(scope="module")
def trained_registry():
    """A registry with two published posterior samples + query docs."""
    import jax
    import jax.numpy as jnp

    from repro.core import hdp as H
    from repro.data.synthetic import planted_topics_corpus
    from repro.serve import snapshot as SNAP
    from repro.serve.registry import SnapshotRegistry

    K, V = 12, 48
    rng = np.random.default_rng(0)
    corpus, _ = planted_topics_corpus(rng, D=40, V=V, K_true=3,
                                      doc_len=(10, 20))
    cfg = H.HDPConfig(K=K, V=V, bucket=K, z_impl="sparse", hist_cap=32)
    tokens = jnp.asarray(corpus.tokens[:32])
    mask = jnp.asarray(corpus.mask[:32])
    state = H.init_state(jax.random.key(0), tokens, mask, cfg)
    step = jax.jit(lambda s: H.gibbs_iteration(s, tokens, mask, cfg))
    for _ in range(6):
        state = step(state)
    snap1 = SNAP.snapshot_from_state(state, cfg)
    for _ in range(3):
        state = step(state)
    snap2 = SNAP.snapshot_from_state(state, cfg)
    d = tempfile.mkdtemp()
    reg = SnapshotRegistry(d)
    reg.publish(snap1)
    reg.publish(snap2)
    docs = [corpus.tokens[i][corpus.mask[i]] for i in range(32, 40)]
    return reg, docs


@pytest.mark.parametrize("workers", [1, 2])
def test_fleet_stats_under_ensemble(trained_registry, workers):
    import jax

    from repro.serve.fleet import ServeFleet

    reg, docs = trained_registry
    with ServeFleet(
        reg, workers=workers, slots=3, burnin=4, impl="sparse",
        buckets=(16, 32), base_key=jax.random.key(1), ensemble=2,
        slo_ms=60_000.0,
    ) as fleet:
        for doc in docs:
            fleet.submit(doc)
        out = fleet.run()
    # read stats after close(): workers have joined, so their subtask
    # counters (incremented after router.post) are final
    s = fleet.stats_summary()

    assert len(out) == len(docs)
    assert s["workers"] == workers and s["ensemble"] == 2
    # request-level completion counts each ensemble request ONCE
    assert s["completed"] == len(docs)
    assert s["latency_window"] == len(docs)
    assert s["latencies_dropped"] == 0
    # SLO accounting: every completion classified, none unaccounted
    assert s["slo_ms"] == 60_000.0
    assert s["slo_ok"] + s["slo_miss"] == len(docs)
    assert s["slo_ok"] == len(docs)  # a minute-scale SLO cannot miss here
    # subtask-level counters see ensemble * requests units of work
    assert sum(w["completed"] for w in s["per_worker"]) == 2 * len(docs)

    M = obs.metrics()
    # per-bucket end-to-end latency histograms cover every request
    lat_total = sum(
        M.get("serve.latency_ms", bucket=b).count
        for b in (16, 32) if M.get("serve.latency_ms", bucket=b)
    )
    assert lat_total == len(docs)
    # per-bucket SLO counters agree with the router's tallies
    ok_total = sum(
        M.get("serve.slo_ok", bucket=b).value
        for b in (16, 32) if M.get("serve.slo_ok", bucket=b)
    )
    assert ok_total == s["slo_ok"]
    # engine-side queue-wait observations: one per admitted subtask
    qw_total = sum(
        m.count for key, m in M._metrics.items()
        if key[0] == "serve.queue_wait_ms"
    )
    assert qw_total == 2 * len(docs)
    # queue-depth gauges exist and have drained back to empty
    depth = [M.get("serve.queue_depth", bucket=b) for b in (16, 32)]
    assert any(g is not None for g in depth)
    assert all(g.value == 0 for g in depth if g is not None)


def test_engine_latency_window_accounting():
    from repro.serve.engine import EngineStats

    st = EngineStats()
    st._LAT_CAP = 8  # shrink the window cap for the test
    for i in range(10):
        st.record_latency(float(i))
    assert len(st.latencies_s) + st.latencies_dropped == 10
    assert st.latencies_dropped == 4  # half the cap evicted once
    s = st.summary()
    assert s["latency_window"] == len(st.latencies_s)
    assert s["latencies_dropped"] == 4


def test_router_slo_accounting_survives_latency_eviction():
    """Satellite: the bounded latency window evicts raw samples under
    load, but SLO tallies are classified at completion time and must
    NOT shrink with the window — at ensemble >= 2, where each request
    completes only once both subtask versions post."""
    from repro.serve.router import AdmissionRouter

    n_req = 10
    r = AdmissionRouter(buckets=(16,), max_pending=64, slo_ms=60_000.0)
    r._LAT_CAP = 8  # instance attr shadows the class cap
    for rid in range(n_req):
        r.submit(rid, np.arange(4), versions=(1, 2))
    while True:
        tasks = r.pull(64, timeout=0.0)
        if not tasks:
            break
        for t in tasks:
            r.post(t, np.full(3, 0.5, np.float32))
    out = r.drain(timeout=5.0)
    assert len(out) == n_req

    s = r.latency_summary()
    # raw-window accounting: every completion either retained or
    # counted as evicted — one latency per REQUEST, not per subtask
    assert s["latency_window"] + s["latencies_dropped"] == n_req
    assert s["latencies_dropped"] == 4  # half the cap evicted once
    # SLO accounting: immune to eviction, every request classified once
    assert s["slo_ok"] + s["slo_miss"] == n_req
    assert s["slo_ok"] == n_req  # minute-scale SLO cannot miss here
    assert r.completed_total() == n_req
    # per-bucket registry counters agree with the router's tallies
    M = obs.metrics()
    assert M.get("serve.slo_ok", bucket=16).value == n_req
    assert M.get("serve.slo_miss", bucket=16) is None \
        or M.get("serve.slo_miss", bucket=16).value == 0
    assert M.get("serve.latency_ms", bucket=16).count == n_req
    r.close()


def test_router_slo_validation():
    from repro.serve.router import AdmissionRouter

    with pytest.raises(ValueError):
        AdmissionRouter(buckets=(16,), slo_ms=0)
    r = AdmissionRouter(buckets=(16,), slo_ms=5.0)
    assert r.latency_summary()["slo_ok"] == 0
    assert r.latency_summary()["slo_miss"] == 0


def test_serve_request_trace_spans(trained_registry):
    """--trace on the serve path: per-request async spans pair up and
    carry bucket + engine tags."""
    import jax

    from repro.serve.fleet import ServeFleet

    reg, docs = trained_registry
    tr = obs.enable_tracing()
    with ServeFleet(
        reg, workers=1, slots=3, burnin=4, impl="sparse",
        buckets=(16, 32), base_key=jax.random.key(1),
    ) as fleet:
        for doc in docs:
            fleet.submit(doc)
        fleet.run()
    evs = tr.events()
    begins = [e for e in evs if e["ph"] == "b"]
    ends = [e for e in evs if e["ph"] == "e"]
    # every async begin has a matching end (same name, cat, id)
    key = lambda e: (e["name"], e["cat"], e["id"])
    assert sorted(map(key, begins)) == sorted(map(key, ends))
    router_reqs = [e for e in begins
                   if e["name"] == "request" and e["cat"] == "router"]
    assert len(router_reqs) == len(docs)
    assert all("bucket" in e["args"] for e in router_reqs)
    inflight = [e for e in begins if e["name"] == "request.inflight"]
    assert len(inflight) == len(docs)
    assert all(e["args"]["tag"].startswith("w0.v") for e in inflight)
    # worker engine steps show as complete events on the worker track
    steps = [e for e in evs if e["ph"] == "X" and e["name"] == "engine_step"]
    assert steps
