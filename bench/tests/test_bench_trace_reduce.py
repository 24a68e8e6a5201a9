"""``bench/trace_reduce.py`` on a small profiler trace built here.

Window 0–100 µs (host span ``bench.window``).  Device ops, in µs:
fusion.1 10–30 and fusion.2 20–40 (overlapping: busy 10–40), sort.3 60–70,
and one op 150–160 outside the window.  Busy is 30 + 10 = 40 µs of 100.
Idle gaps: 0–10, 40–60, 70–100; the host was in ``bench.wait`` over 35–65
and ``bench.serve_queries`` over 70–100.
"""

from __future__ import annotations

import pytest

from bench import trace_reduce

US = 1_000_000          # picoseconds per microsecond


def _events(pairs):
    return "".join(
        f"events {{ metadata_id: {m} offset_ps: {a * US} duration_ps: {(b - a) * US} }}\n"
        for m, a, b in pairs)


def _meta(names):
    return "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
        for k, n in names.items())


TRACE = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_events([(10, 10, 45), (11, 55, 75), (11, 150, 160)])} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_events([(1, 10, 30), (2, 20, 40), (3, 60, 70), (3, 150, 160)])} }}
  {_meta({1: "fusion.1", 2: "%fusion.2 = s32[8]{{0}} fusion(s32[8]{{0}} %p)", 3: "sort.3",
          10: "jit_step(1711524293086572984)", 11: "jit_merge"})}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_events([(20, 0, 100), (21, 35, 65), (22, 70, 100), (23, 0, 5)])} }}
  {_meta({20: "bench.window", 21: "bench.wait", 22: "bench.serve_queries",
          23: "PjitFunction(step)"})}
}}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    planes = trace_reduce.read_planes(ProfileData.from_text_proto(TRACE))
    return trace_reduce.reduce(planes)


def test_busy_is_the_union_of_ops_inside_the_window(reduced):
    assert reduced["window_s"] == pytest.approx(100e-6)
    assert reduced["busy_s"] == pytest.approx(40e-6)
    assert reduced["devices"] == 1


def test_top_ops_are_named_by_program(reduced):
    ops = dict(reduced["device_ops"])
    assert ops == pytest.approx({"jit_step/fusion.1": 20e-6, "jit_step/fusion.2": 20e-6,
                                 "jit_merge/sort.3": 10e-6})


def test_gaps_go_to_the_host_span_open_during_them(reduced):
    gaps = reduced["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.serve_queries", "bench.wait", "no bench span"]
    assert [g[1] for g in gaps] == pytest.approx([30e-6, 20e-6, 10e-6])


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce([("/host:CPU", [("python", [("x", 0, 10)])])])
