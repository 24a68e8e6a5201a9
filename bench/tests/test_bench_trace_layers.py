"""``bench/trace_layers.py`` on a small profiler trace built here, the
program's spans left out of ``bench/trace_reduce.py``, and the readers of the
window's query split.

Window 0–100 µs.  Device programs (``XLA Modules``) and their ops, in µs:
``jit__merge_sorted`` 10–40 (``fusion.1`` 10–40 with ``fusion.2`` 15–25
nested), ``jit_bitwise_or`` 50–60, ``jit_dynamic_slice`` 70–75 and
``jit_bitmm_call`` 80–90.  Busy 55 µs; idle 0–10, 40–50, 60–70, 75–80 and
90–100.

Program spans (annotations with a ``span_id``): on the writer's Python line
``txn.apply`` 5–63 with ``stratum`` 45–62 in it, and ``txn.apply`` 95–110,
which ends after the window; on the calling thread's line ``query`` 65–78
with ``device.sync`` 68–77 in it.  The calling thread's launches carry their
``run_id`` on its own line; the writer's are on a runtime line, inside the
consumer of a flow whose producer is on the writer's Python line.
"""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import trace_layers, trace_reduce
from bench.metrics import query_device_wait_ms, query_queue_ms
from bench.tests.test_bench_trace_reduce import TRACE as BENCH_TRACE

US = 1_000_000          # picoseconds per microsecond
STATS = {1: "run_id", 2: "span_id", 3: "_p", 4: "_c"}


def _stat(key, v):
    value = f'str_value: "{v}"' if isinstance(v, str) else f"int64_value: {v}"
    return f"stats {{ metadata_id: {key} {value} }}"


def _events(rows):
    """``(metadata id, start µs, end µs, {stat key: value})`` rows."""
    return "".join(
        f"events {{ metadata_id: {m} offset_ps: {a * US} duration_ps: {(b - a) * US} "
        + " ".join(_stat(k, v) for k, v in st.items()) + " }\n"
        for m, a, b, st in rows)


def _meta(names):
    return "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
        for k, n in names.items()) + "".join(
        f'stat_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
        for k, n in STATS.items())


TRACE = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_events([(10, 10, 40, {1: 101}), (11, 50, 60, {1: 102}), (12, 70, 75, {1: 103}),
              (13, 80, 90, {1: 104})])} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_events([(1, 10, 40, {}), (2, 15, 25, {}), (3, 50, 60, {}), (4, 70, 75, {}),
              (5, 80, 90, {})])} }}
  {_meta({1: "fusion.1", 2: "fusion.2", 3: "or.1", 4: "slice.1", 5: "bitmm.1",
          10: "jit__merge_sorted(1711524293086572984)", 11: "jit_bitwise_or(17)",
          12: "jit_dynamic_slice(18)", 13: "jit_bitmm_call(19)"})}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_events([(20, 0, 100, {}), (21, 65, 78, {2: 1}), (22, 68, 77, {2: 2}),
              (30, 69, 70, {1: 103}), (30, 79, 80, {1: 104})])} }}
  lines {{ id: 2 name: "python" timestamp_ns: 0
    {_events([(23, 5, 63, {2: 3}), (31, 6, 7, {3: 9001}), (24, 45, 62, {2: 4}),
              (31, 47, 48, {3: 9002}), (23, 95, 110, {2: 5})])} }}
  lines {{ id: 3 name: "" timestamp_ns: 0
    {_events([(32, 6, 9, {4: 9001}), (30, 7, 8, {1: 101}),
              (32, 47, 50, {4: 9002}), (30, 48, 49, {1: 102})])} }}
  {_meta({20: "bench.window", 21: "query", 22: "device.sync", 23: "txn.apply",
          24: "stratum", 30: "DoEnqueueProgram",
          31: "PJRT_LoadedExecutable_Execute linkage",
          32: "PJRT_LoadedExecutable_Execute"})}
}}
"""

RECORDS = {1: ("query", {"rid": 9}), 2: ("device.sync", {"what": "query_rows"}),
           3: ("txn.apply", {"rids": (4,)}), 4: ("stratum", {"mode": "bitmatrix"}),
           5: ("txn.apply", {"rids": (5,)})}


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData

    return trace_layers.read_planes(ProfileData.from_text_proto(TRACE))


def test_device_time_goes_to_its_program_or_launching_span(planes):
    out = trace_layers.reduce(planes, RECORDS)
    assert out["window_s"] == pytest.approx(100e-6)
    assert out["busy_s"] == pytest.approx(55e-6)
    # merge by its program (nested op counted once), pbme by its program
    # (jit_bitmm_call) and by the bitmatrix stratum that launched
    # jit_bitwise_or, found through the writer's flow
    assert out["layers"] == pytest.approx(
        {"tuple.merge": 30e-6, "pbme": 20e-6, "unscoped": 5e-6})


def test_without_records_a_launch_has_no_mode_and_stays_unscoped(planes):
    out = trace_layers.reduce(planes)
    assert out["layers"] == pytest.approx(
        {"tuple.merge": 30e-6, "pbme": 10e-6, "unscoped": 15e-6})


def test_idle_time_inside_each_program_span(planes):
    out = trace_layers.reduce(planes, RECORDS)
    spans = out["spans"]
    assert set(spans) == {"txn.apply", "stratum", "query", "device.sync"}
    assert spans["txn.apply"]["count"] == 1          # the second ends after the close
    assert spans["txn.apply"]["seconds"] == pytest.approx(58e-6)
    assert spans["txn.apply"]["idle_s"] == pytest.approx(18e-6)
    assert spans["stratum"]["idle_s"] == pytest.approx(7e-6)
    assert spans["query"]["idle_s"] == pytest.approx(8e-6)
    assert spans["device.sync"]["idle_s"] == pytest.approx(4e-6)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace_layers.reduce([("/host:CPU", [("python", [("x", 0, 10, {})])])])


def test_program_spans_do_not_touch_the_benchmarks_reduction():
    """``trace_reduce`` reads only ``bench.*`` host events: the program's
    annotations, here overlapping every idle gap, change nothing."""
    from jax.profiler import ProfileData

    bare = trace_reduce.read_planes(ProfileData.from_text_proto(BENCH_TRACE))
    extra = [("txn.apply", 0, 100_000), ("stratum", 36_000, 64_000),
             ("device.sync", 72_000, 99_000), ("PjitFunction(merge)", 1_000, 2_000)]
    annotated = [(p, [(ln, evs + extra if not p.startswith("/device:") else evs)
                      for ln, evs in lines]) for p, lines in bare]
    assert annotated != bare
    assert trace_reduce.reduce(annotated) == trace_reduce.reduce(bare)


def _span(name, dur_ms, **args):
    return {"name": name, "args": args, "start_ns": 0, "dur_ns": int(dur_ms * 1e6)}


def test_query_split_readers():
    run = SimpleNamespace(spans=[
        _span("serve.queries", 30, batch=2),
        _span("query", 20, rid=1, queue_wait_s=0.5),
        _span("device.sync", 18, what="query_rows"),
        _span("query", 2, rid=2, queue_wait_s=1.5),
        _span("device.sync", 1, what="query_rows"),
        _span("device.sync", 7, what="to_numpy"),
    ])
    assert query_queue_ms.read(run) == pytest.approx(1000.0)
    assert query_device_wait_ms.read(run) == pytest.approx(9.5)


def test_query_split_readers_read_nothing_where_the_program_records_nothing():
    """A program whose ``query`` spans carry no queue wait, or a window that
    served no query, gives no value rather than an error."""
    older = SimpleNamespace(spans=[_span("query", 2, rid=1),
                                   _span("device.sync", 1, what="query_rows")])
    assert query_queue_ms.read(older) is None
    assert query_device_wait_ms.read(older) == pytest.approx(1.0)
    empty = SimpleNamespace(spans=[_span("txn.apply", 400)])
    assert query_queue_ms.read(empty) is None
    assert query_device_wait_ms.read(empty) is None


def test_a_traced_run_reports_the_query_split():
    """The program's spans reach both readers through the harness: a query's
    device wait is part of its service (the ``query`` span holds its
    lookup's ``device.sync``)."""
    from bench.tests._tiny import run_tiny

    out, _run = run_tiny(trace=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["query_queue_ms"] >= 0
    assert 0 < m["query_device_wait_ms"] <= m["query_service_ms"]
