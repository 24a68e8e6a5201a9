"""Observability subsystem: tracer, metrics registry, shared stats, wiring.

Covers the pieces in isolation (span nesting, thread-local buffers,
histogram bucket math, exposition formats) plus the end-to-end promise: a
traced server transaction exports a valid Chrome trace-event span tree and
bumps the server metrics, while the disabled-mode fast path stays no-op.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.stats import latency_summary, nearest_rank, percentile
from repro.obs.trace import NOOP_SPAN, Tracer

# --------------------------------------------------------------------------
# shared percentile helpers (repro.obs.stats)
# --------------------------------------------------------------------------


def test_nearest_rank_convention():
    vals = [1.0, 2.0, 3.0]
    assert nearest_rank(vals, 0.50) == 2.0        # ceil(1.5)-1 = index 1
    assert nearest_rank(vals, 0.95) == 3.0
    assert nearest_rank([7.0], 0.50) == 7.0
    assert nearest_rank([1.0, 2.0], 1.0) == 2.0
    assert nearest_rank([1.0, 2.0], 0.01) == 1.0  # rank floors at 1


def test_nearest_rank_rejects_bad_input():
    with pytest.raises(ValueError):
        nearest_rank([], 0.5)
    with pytest.raises(ValueError):
        nearest_rank([1.0], 0.0)
    with pytest.raises(ValueError):
        nearest_rank([1.0], 1.5)


def test_percentile_sorts():
    assert percentile([3.0, 1.0, 2.0], 0.50) == 2.0


def test_latency_summary_matches_server_stats_shape():
    out = latency_summary([0.010, 0.020, 0.030])
    assert out == {
        "count": 3,
        "p50_ms": pytest.approx(20.0),
        "p95_ms": pytest.approx(30.0),
        "max_ms": pytest.approx(30.0),
    }
    assert latency_summary([]) == {"count": 0}


# --------------------------------------------------------------------------
# tracer
# --------------------------------------------------------------------------


def test_span_nesting_and_parent_ids():
    tr = Tracer()
    tr.enable()
    with tr.span("outer", "t", a=1) as outer:
        with tr.span("inner", "t") as inner:
            inner.set(b=2)
        tr.instant("mark", "t")
    spans = tr.spans()
    by_name = {s.name: s for s in spans}
    assert set(by_name) == {"outer", "inner", "mark"}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["mark"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].parent_id == 0        # 0 marks a root span
    assert by_name["outer"].args["a"] == 1
    assert by_name["inner"].args["b"] == 2
    # closed spans have a measured duration; instants stay open-marked
    assert by_name["outer"].dur_ns >= by_name["inner"].dur_ns >= 0
    assert by_name["mark"].dur_ns == -1
    assert outer.span_id != inner.span_id


def test_span_exception_safe():
    tr = Tracer()
    tr.enable()
    with pytest.raises(RuntimeError):
        with tr.span("outer", "t"):
            with tr.span("inner", "t"):
                raise RuntimeError("boom")
    with tr.span("after", "t"):
        pass
    after = {s.name: s for s in tr.spans()}["after"]
    assert after.parent_id == 0                   # stack unwound on raise


def test_thread_isolation():
    tr = Tracer()
    tr.enable()
    ready = threading.Barrier(2)

    def worker(tag):
        ready.wait()
        for i in range(50):
            with tr.span(f"{tag}", "t", i=i):
                pass

    ts = [threading.Thread(target=worker, args=(f"w{n}",)) for n in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    spans = tr.spans()
    assert len(spans) == 100
    for s in spans:
        assert s.parent_id == 0                   # no cross-thread parents
    tids = {s.tid for s in spans}
    assert len(tids) == 2
    # each thread's spans live in its own buffer (names don't interleave tids)
    for tid in tids:
        assert len({s.name for s in spans if s.tid == tid}) == 1


def test_disabled_mode_is_noop():
    tr = Tracer()
    assert tr.span("x", "t", big=list(range(100))) is NOOP_SPAN
    assert tr.instant("x", "t") is None
    with tr.span("x"):
        pass
    assert tr.spans() == []                       # nothing buffered
    NOOP_SPAN.set(a=1)                            # attribute sink is free
    assert not hasattr(NOOP_SPAN, "args")


def test_disable_reenables_cleanly():
    tr = Tracer()
    tr.enable()
    with tr.span("a"):
        pass
    tr.disable()
    with tr.span("b"):
        pass
    tr.enable(clear=False)
    assert {s.name for s in tr.spans()} == {"a"}
    tr.enable()                                   # default clears
    assert tr.spans() == []


def test_buffer_bound():
    tr = Tracer()
    tr.enable(max_spans_per_thread=16)
    for i in range(100):
        tr.instant("e", "t", i=i)
    spans = tr.spans()
    assert len(spans) <= 32                       # trimmed at 2x watermark
    assert spans[-1].args["i"] == 99              # newest survive


def test_finished_threads_keep_their_spans():
    """Short-lived threads, each started after the last one ended, so the
    OS can hand every one the same ident: all of their spans survive."""
    tr = Tracer()
    tr.enable()
    for i in range(50):
        th = threading.Thread(target=lambda i=i: tr.span("w", "t", i=i).__exit__())
        th.start()
        th.join()
    assert sorted(s.args["i"] for s in tr.spans()) == list(range(50))
    tr.clear()
    assert tr.spans() == []
    assert len(tr._buffers) <= 1              # finished threads' buffers go


def test_annotate_hook_wraps_every_span_on_its_thread():
    calls = []

    class Annotation:
        def __init__(self, name, **stats):
            self.key = (name, stats["span_id"])

        def __enter__(self):
            calls.append(("enter", *self.key, threading.get_ident()))
            return self

        def __exit__(self, *exc):
            calls.append(("exit", *self.key, threading.get_ident()))

    tr = Tracer()
    tr.enable(annotate=Annotation)
    with tr.span("outer", "t"):
        with tr.span("inner", "t"):
            pass
    th = threading.Thread(target=lambda: tr.span("other", "t").__exit__())
    th.start()
    th.join()
    tr.instant("mark", "t")                   # instants open no annotation
    spans = {s.name: s for s in tr.spans() if s.dur_ns >= 0}
    me = threading.get_ident()
    o, i, x = spans["outer"].span_id, spans["inner"].span_id, spans["other"].span_id
    assert calls == [
        ("enter", "outer", o, me), ("enter", "inner", i, me),
        ("exit", "inner", i, me), ("exit", "outer", o, me),
        ("enter", "other", x, th.ident), ("exit", "other", x, th.ident),
    ]
    tr.disable()
    with tr.span("off", "t"):
        pass
    tr.enable(clear=False)                    # enabled without the hook
    with tr.span("plain", "t"):
        pass
    assert len(calls) == 6


def test_chrome_export_roundtrip(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("outer", "cat", k="v"):
        with tr.span("inner", "cat"):
            pass
        tr.instant("mark", "cat")
    path = tmp_path / "trace.json"
    exported = tr.export_chrome(str(path))
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(exported))
    evs = loaded["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert phases == {"M", "X", "i"}
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert xs["inner"]["args"]["parent_id"] == xs["outer"]["args"]["span_id"]
    assert xs["outer"]["ts"] <= xs["inner"]["ts"]
    assert (
        xs["inner"]["ts"] + xs["inner"]["dur"]
        <= xs["outer"]["ts"] + xs["outer"]["dur"] + 1
    )
    assert xs["outer"]["args"]["k"] == "v"
    mark = next(e for e in evs if e["ph"] == "i")
    assert mark["s"] == "t"


def test_trace_decorator():
    tr = Tracer()
    tr.enable()

    @tr.trace("decorated", "t")
    def f(x):
        return x * 2

    assert f(21) == 42
    assert {s.name for s in tr.spans()} == {"decorated"}


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------


def test_counter_monotonic():
    c = Counter("n", "")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_and_callback():
    g = Gauge("g", "")
    g.set(5)
    g.inc(2)
    g.dec(3)
    assert g.value == 4
    backing = [0.0]
    g2 = Gauge("g2", "", fn=lambda: backing[0])
    backing[0] = 7.5
    assert g2.value == 7.5                        # read at collect time


def test_histogram_bucket_math():
    h = Histogram("h", "", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    # le-inclusive: 0.1 lands in the 0.1 bucket, 1.0 in the 1.0 bucket
    assert snap["buckets"] == {"0.1": 2, "1.0": 4, "10.0": 5, "+Inf": 6}
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(56.65)


def test_histogram_percentile_from_bounds():
    h = Histogram("h", "", buckets=(0.1, 1.0, 10.0))
    assert h.percentile(0.5) == 0.0               # empty
    for _ in range(9):
        h.observe(0.05)
    h.observe(5.0)
    assert h.percentile(0.50) == 0.1              # bucket upper bound
    assert h.percentile(0.99) == 10.0
    h.observe(100.0)                              # +Inf observation
    assert h.percentile(1.0) == 10.0              # largest finite bound


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram("h", "", buckets=())
    with pytest.raises(ValueError):
        Histogram("h", "", buckets=(1.0, 1.0))
    # out-of-order bounds normalize (sorted at construction), not raise
    assert Histogram("h", "", buckets=(2.0, 1.0)).bounds == (1.0, 2.0)


def test_registry_get_or_create_and_labels():
    reg = MetricsRegistry()
    a = reg.counter("hits", "h", labels={"kind": "q"})
    b = reg.counter("hits", "h", labels={"kind": "q"})
    c = reg.counter("hits", "h", labels={"kind": "t"})
    assert a is b and a is not c
    with pytest.raises(ValueError):
        reg.gauge("hits", labels={"kind": "q"})   # type mismatch
    a.inc()
    snap = reg.snapshot()
    assert snap['hits{kind="q"}'] == 1.0
    assert snap['hits{kind="t"}'] == 0.0


def test_prometheus_exposition_parses():
    from benchmarks.obs_smoke import validate_prometheus

    reg = MetricsRegistry()
    reg.counter("req_total", "requests", labels={"kind": "q"}).inc(3)
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_seconds", "latency", buckets=DEFAULT_BUCKETS)
    h.observe(0.003)
    text = reg.to_prometheus()
    families = validate_prometheus(text)
    assert families == {"req_total", "depth", "lat_seconds"}
    assert '# TYPE lat_seconds histogram' in text
    assert 'req_total{kind="q"} 3' in text
    # cumulative buckets end at +Inf == count
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text


def test_registry_json_snapshot_roundtrips():
    reg = MetricsRegistry()
    reg.counter("c", "").inc()
    reg.histogram("h", "", buckets=(1.0,)).observe(0.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["c"] == 1.0
    assert snap["h"]["buckets"]["+Inf"] == 1


# --------------------------------------------------------------------------
# end-to-end: traced server transaction + metric increments
# --------------------------------------------------------------------------


def _chain(n):
    idx = np.arange(n, dtype=np.int32)
    return np.stack([idx, idx + 1], axis=1)


def test_server_txn_span_tree_and_metrics(tmp_path):
    from repro.core.engine import EngineConfig
    from repro.obs.trace import TRACER
    from repro.serve_datalog import DatalogServer, MaterializedInstance

    prog = """
    tc(x,y) :- arc(x,y).
    tc(x,y) :- tc(x,z), arc(z,y).
    """
    arc = _chain(24)
    # hold out a MIDDLE edge so the re-insert stays inside the materialized
    # active domain (incremental Δ pass, not the full-rebuild path)
    base = np.concatenate([arc[:10], arc[11:]])
    inst = MaterializedInstance(
        prog, {"arc": base}, EngineConfig(backend="tuple")
    )
    srv = DatalogServer(inst, durability=str(tmp_path / "root"))
    TRACER.enable()
    try:
        srv.submit_txn([("insert", "arc", arc[10:11])])
        srv.submit_query("tc", src=0)
        srv.run()
        trace = TRACER.export_chrome()
    finally:
        TRACER.disable()
        srv.close()

    evs = [e for e in trace["traceEvents"] if e["ph"] in ("X", "i")]
    names = {e["name"] for e in evs}
    assert {
        "enqueue", "admission", "writer.apply", "txn.apply", "stratum",
        "iteration", "rule", "wal.fsync", "epoch.publish", "serve.queries",
    } <= names

    by_id = {e["args"]["span_id"]: e for e in evs if e["ph"] == "X"}

    def ancestors(e):
        while e["args"].get("parent_id") in by_id:
            e = by_id[e["args"]["parent_id"]]
            yield e["name"]

    # the span TREE: stratum under txn.apply under writer.apply; iterations
    # under their stratum; WAL fsync + epoch publish inside the apply
    for e in by_id.values():
        chain = list(ancestors(e))
        if e["name"] == "stratum":
            assert "txn.apply" in chain and "writer.apply" in chain
        if e["name"] == "iteration":
            assert "stratum" in chain
        if e["name"] in ("wal.fsync", "epoch.publish"):
            assert "writer.apply" in chain
    it = next(e for e in by_id.values() if e["name"] == "iteration")
    assert "deltas" in it["args"]                 # per-iteration Δ sizes

    m = srv.metrics()
    assert m['datalog_requests_total{kind="txn"}'] == 1.0
    assert m['datalog_requests_total{kind="query"}'] == 1.0
    assert m["datalog_rows_inserted_total"] == 1.0
    assert m["datalog_rows_derived_total"] >= 1.0
    assert m["datalog_update_groups_total"] == 1.0
    assert m["datalog_wal_fsync_seconds"]["count"] >= 1
    assert m["datalog_query_seconds"]["count"] == 1
    assert m["datalog_update_seconds"]["count"] == 1
    assert m["datalog_queue_depth"] == 0.0
    assert 0.0 <= m["datalog_plan_cache_hit_rate"] <= 1.0
    json.dumps(m)                                 # snapshot stays JSON-clean
    assert "datalog_requests_total" in srv.metrics_prometheus()


def test_one_requests_spans_share_its_id():
    from repro.core.engine import EngineConfig
    from repro.obs.trace import TRACER
    from repro.serve_datalog import DatalogServer, MaterializedInstance

    arc = _chain(12)
    base = np.concatenate([arc[:5], arc[6:]])
    inst = MaterializedInstance(
        "tc(x,y) :- arc(x,y).\ntc(x,y) :- tc(x,z), arc(z,y).",
        {"arc": base}, EngineConfig(backend="tuple"),
    )
    srv = DatalogServer(inst)
    TRACER.enable()
    try:
        t1 = srv.submit_txn([("insert", "arc", arc[5:6])])
        t2 = srv.submit_txn([("insert", "arc", arc[5:6])])
        q = srv.submit_query("tc", src=0)
        srv.run()
        spans = TRACER.spans()
    finally:
        TRACER.disable()
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert [s.args["rids"] for s in by["writer.apply"]] == [(t1, t2)]
    assert [s.args["rids"] for s in by["txn.apply"]] == [(t1, t2)]
    (query,) = by["query"]
    assert query.args["rid"] == q and query.args["queue_wait_s"] >= 0
    # one device.sync per lookup, under its query span
    (sync,) = [s for s in by["device.sync"] if s.parent_id == query.span_id]
    assert sync.args["what"] == "query_rows"


def test_queue_wait_is_labelled_by_kind():
    from repro.core.engine import EngineConfig
    from repro.serve_datalog import DatalogServer, MaterializedInstance

    arc = _chain(12)
    inst = MaterializedInstance(
        "tc(x,y) :- arc(x,y).\ntc(x,y) :- tc(x,z), arc(z,y).",
        {"arc": arc[:-1]}, EngineConfig(backend="tuple"),
    )
    srv = DatalogServer(inst)
    for s in range(3):
        srv.submit_query("tc", src=s)
    srv.submit_txn([("insert", "arc", arc[-1:])])
    srv.run()
    m = srv.metrics()
    assert m['datalog_queue_wait_seconds{kind="query"}']["count"] == 3
    assert m['datalog_queue_wait_seconds{kind="txn"}']["count"] == 1
    assert m['datalog_queue_wait_seconds{kind="insert"}']["count"] == 0
    assert "datalog_queue_wait_seconds" not in m


def test_server_stats_snapshot_under_concurrent_mutation():
    """Reader iteration must not race writer appends (the deque bug)."""
    from repro.serve_datalog.server import RequestRecord, ServerStats

    stats = ServerStats()
    stop = threading.Event()
    errs = []

    def writer():
        i = 0
        while not stop.is_set():
            stats.add(RequestRecord(i, "query", "tc", 1, 0.0, 0.001))
            i += 1

    def reader():
        while not stop.is_set():
            try:
                stats.latency("query")
                stats.snapshot()
            except RuntimeError as e:              # pragma: no cover
                errs.append(e)
                return

    ts = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader) for _ in range(2)
    ]
    for t in ts:
        t.start()
    import time

    time.sleep(0.5)
    stop.set()
    for t in ts:
        t.join()
    assert not errs
    lat = stats.latency("query")
    assert lat["count"] > 0 and lat["p50_ms"] == pytest.approx(1.0)
