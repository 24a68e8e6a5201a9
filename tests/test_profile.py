"""EXPLAIN/ANALYZE profiling subsystem: estimates, profiles, gating.

Covers the plan-time estimator (System-R style independence-assumption
cardinalities, recursive-stratum fixpoint iteration, first-order delta
scaling), the runtime profile assembly from tracer spans (the acceptance
invariant: per-rule span deltas sum to the engine's reported Δ totals),
cross-request isolation, the slow-query ring, the profile-off fast path
staying bit-for-bit, the Prometheus escaping fixes, and the CI perf gate.
"""

from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.compare_trajectory import main as gate_main
from benchmarks.trajectory import gate, higher_is_better
from repro.core.engine import EngineConfig
from repro.data.program_facts import csda_facts
from repro.obs.explain import estimate_plan, estimate_query_rows
from repro.obs.metrics import MetricsRegistry
from repro.obs.profile import RATIO_BUCKETS, misestimation_ratio
from repro.obs.trace import TRACER
from repro.serve_datalog import (
    DatalogServer,
    MaterializedInstance,
    ServerLimits,
)

CSDA = """
null(x,y) :- nullEdge(x,y).
null(x,y) :- null(x,w), arc(w,y).
"""

TC = """
tc(x,y) :- arc(x,y).
tc(x,y) :- tc(x,z), arc(z,y).
"""


def _csda_instance(n=24, seed=3):
    facts = csda_facts(n, seed=seed)
    return MaterializedInstance(
        CSDA, facts, config=EngineConfig(backend="tuple")
    )


# --------------------------------------------------------------------------
# plan-time estimator (repro.obs.explain)
# --------------------------------------------------------------------------


def test_estimate_copy_rule_is_input_size():
    inst = _csda_instance()
    est = estimate_plan(
        inst.plan, sizes={"nullEdge": 7.0, "arc": 50.0}, domain=24
    )
    s0 = est.strata[0]
    copy = next(r for r in s0.rules if "nullEdge" in r.inputs)
    # null(x,y) :- nullEdge(x,y). projects nothing away: est == |nullEdge|
    assert copy.est_rows == pytest.approx(7.0)


def test_estimate_join_uses_independence_assumption():
    inst = MaterializedInstance(
        TC,
        {"arc": np.array([[0, 1], [1, 2]], np.int32)},
        config=EngineConfig(backend="tuple"),
    )
    est = estimate_plan(inst.plan, sizes={"arc": 10.0}, domain=20)
    s0 = est.strata[0]
    join = next(r for r in s0.rules if "tc" in r.inputs)
    # tc(x,z), arc(z,y): |tc|*|arc|/domain at the first recursive round,
    # where tc starts from the copy rule's estimate (|arc| = 10)
    assert join.inputs["arc"] == pytest.approx(10.0)
    assert join.est_rows <= 20.0 * 20.0          # capped at domain^arity
    assert est.stratum(0).recursive
    assert est.stratum(0).est_rows >= 10.0       # at least the base rule


def test_estimate_recursive_stratum_converges_and_caps():
    inst = MaterializedInstance(
        TC,
        {"arc": np.array([[0, 1]], np.int32)},
        config=EngineConfig(backend="tuple"),
    )
    # dense graph: the fixpoint must stop at the domain^arity cap, finite
    est = estimate_plan(inst.plan, sizes={"arc": 64.0}, domain=8)
    assert est.strata[0].est_rows <= 64.0
    assert est.strata[0].est_rows > 0
    assert np.isfinite(est.total_cost())


def test_scaled_delta_first_order():
    inst = _csda_instance()
    est = estimate_plan(
        inst.plan, sizes={"nullEdge": 10.0, "arc": 100.0}, domain=50
    )
    full = est.strata[0].est_rows
    # changing 10% of an input predicts ~10% of the stratum's rows
    scaled = est.scaled_delta({"arc": 10.0})
    assert 0 in scaled
    assert scaled[0] == pytest.approx(full * 0.1)
    # untouched inputs predict nothing
    assert est.scaled_delta({"unrelated": 5.0}) == {}
    # a full-size delta saturates at the full estimate
    assert est.scaled_delta({"arc": 1000.0})[0] == pytest.approx(full)


def test_estimate_query_rows_bounds():
    # unbounded scan: everything
    assert estimate_query_rows(100.0, 10, {}) == pytest.approx(100.0)
    # one point bound: 1/domain selectivity
    assert estimate_query_rows(100.0, 10, {0: (3, 3)}) == pytest.approx(10.0)
    # a range bound: (hi-lo+1)/domain
    assert estimate_query_rows(100.0, 10, {0: (2, 6)}) == pytest.approx(50.0)


def test_misestimation_ratio_smoothing():
    assert misestimation_ratio(0, 0) == 1.0
    assert misestimation_ratio(99, 9) == 10.0
    assert misestimation_ratio(9, 99) == 0.1
    assert RATIO_BUCKETS == tuple(sorted(RATIO_BUCKETS))


def test_plan_estimate_renders_and_serialises():
    inst = _csda_instance()
    est = inst.explain()
    txt = est.render_text()
    assert "stratum 0" in txt and "est_rows≈" in txt and "plan " in txt
    doc = est.to_json()
    json.dumps(doc)
    assert doc["strata"][0]["rules"]


# --------------------------------------------------------------------------
# ANALYZE: profile assembly (the acceptance invariant)
# --------------------------------------------------------------------------


def test_profiled_txn_rule_deltas_sum_to_engine_totals():
    inst = _csda_instance()
    srv = DatalogServer(inst)
    new = np.array([[0, 3], [3, 7]], np.int32)
    rid = srv.submit_txn([("insert", "nullEdge", new)], profile=True)
    srv.run()
    prof = srv.profile(rid)
    st = srv.done[rid]
    # the invariant: per-rule span deltas == per-stratum attribution ==
    # the engine's reported Δ total
    assert prof.rule_delta_total() == st.derived
    assert sum(st.derived_by_stratum.values()) == st.derived
    for sp in prof.strata:
        assert sp.rule_delta_total() == st.derived_by_stratum[sp.index]
        assert sp.actual_rows == st.derived_by_stratum[sp.index]
    assert prof.derived == st.derived
    assert prof.epoch == st.epoch
    assert prof.kind == "txn"
    # estimates rode along and produce finite ratios
    assert any(sp.est_rows is not None for sp in prof.strata)
    assert all(
        sp.ratio is None or np.isfinite(sp.ratio) for sp in prof.strata
    )
    # renderers hold their contract
    txt = prof.render_text()
    assert f"profile rid={rid}" in txt and "stratum 0" in txt
    json.dumps(prof.to_json())


def test_profiled_query_estimate_vs_actual():
    inst = _csda_instance()
    srv = DatalogServer(inst)
    qid = srv.submit_query("null", profile=True)
    srv.run()
    prof = srv.profile(qid)
    assert prof.kind == "query"
    assert prof.rows == len(srv.done[qid])
    assert prof.est_rows is not None and prof.est_rows > 0
    assert prof.ratio == pytest.approx(
        misestimation_ratio(prof.rows, prof.est_rows)
    )
    prom = srv.metrics_prometheus()
    assert 'datalog_misestimation_ratio_count{level="query"} 1' in prom


@pytest.mark.parametrize("bound", [{"src": 0}, {"dst": 5}])
def test_profiled_lookup_syncs_under_one_device_sync_span(bound, monkeypatch):
    """A bound-first-column lookup (two binary searches, then the slice) and
    a selection (its count, then the rows) each read the device under one
    ``device.sync`` span, and the profile's ``device_sync_seconds`` is it."""
    inst = _csda_instance()
    srv = DatalogServer(inst)
    searched_in = []
    search = jnp.searchsorted

    def spy(*a, **k):
        searched_in.append(getattr(TRACER.current(), "name", None))
        return search(*a, **k)

    monkeypatch.setattr(jnp, "searchsorted", spy)
    qid = srv.submit_query("null", profile=True, **bound)
    srv.run()
    if "src" in bound:
        assert searched_in == ["device.sync"] * 2
    prof = srv.profile(qid)
    syncs = [n for r in prof.roots for n in r.walk() if n.name == "device.sync"]
    assert [n.attrs["what"] for n in syncs] == ["query_rows"]
    assert prof.device_sync_seconds == syncs[0].seconds > 0
    assert prof.rows == len(srv.done[qid])


def test_concurrent_profiles_do_not_leak_across_requests():
    inst = _csda_instance()
    srv = DatalogServer(inst)
    new = np.array([[1, 5]], np.int32)
    tid = srv.submit_txn([("insert", "nullEdge", new)], profile=True)
    q1 = srv.submit_query("null", profile=True)
    q2 = srv.submit_query("null", src=0, profile=True)
    srv.run()
    tprof, p1, p2 = srv.profile(tid), srv.profile(q1), srv.profile(q2)
    # the query profiles carry no evaluation strata and exactly their own
    # result cardinality; the txn profile carries no query span
    assert p1.strata == [] and p2.strata == []
    assert p1.rows == len(srv.done[q1])
    assert p2.rows == len(srv.done[q2])
    names_t = {n.name for root in tprof.roots for n in root.walk()}
    names_q = {n.name for root in p1.roots for n in root.walk()}
    assert "query" not in names_t
    assert "stratum" not in names_q and "rule" not in names_q
    assert tprof.rule_delta_total() == srv.done[tid].derived


def test_profile_requires_opt_in_and_is_bounded():
    inst = _csda_instance()
    srv = DatalogServer(inst)
    qid = srv.submit_query("null")
    srv.run()
    with pytest.raises(KeyError):
        srv.profile(qid)
    with pytest.raises(KeyError):
        srv.profile(10_000)


def test_profile_off_results_bit_for_bit_unchanged():
    new = np.array([[2, 9], [9, 4]], np.int32)

    def run(profile):
        inst = _csda_instance()
        srv = DatalogServer(inst)
        tid = srv.submit_txn([("insert", "nullEdge", new)], profile=profile)
        srv.run()
        qid = srv.submit_query("null", profile=profile)
        srv.run()
        return srv.done[qid], srv.done[tid]

    plain_q, plain_t = run(False)
    prof_q, prof_t = run(True)
    assert np.array_equal(plain_q, prof_q)
    assert plain_t.derived == prof_t.derived
    assert plain_t.epoch == prof_t.epoch
    # and profiling leaves the global tracer the way it found it
    assert not TRACER.enabled


# --------------------------------------------------------------------------
# slow-query capture
# --------------------------------------------------------------------------


def test_slow_query_threshold_captures_and_ring_is_bounded():
    inst = _csda_instance()
    lim = ServerLimits(slow_query_threshold=0.0, slow_query_log=2)
    srv = DatalogServer(inst, limits=lim)
    for _ in range(4):                 # every sojourn exceeds 0.0s
        srv.submit_query("null")
        srv.run()
    slow = srv.slow_queries()
    assert len(slow) == 2              # ring evicted the two oldest
    assert all(p.slow for p in slow)
    assert all(p.sojourn_seconds > 0.0 for p in slow)
    prom = srv.metrics_prometheus()
    assert "datalog_slow_queries_total 4" in prom


def test_no_threshold_means_no_slow_captures():
    inst = _csda_instance()
    srv = DatalogServer(inst)
    srv.submit_query("null", profile=True)
    srv.run()
    assert srv.slow_queries() == []


def test_high_threshold_profiles_but_does_not_capture():
    inst = _csda_instance()
    lim = ServerLimits(slow_query_threshold=1e9)
    srv = DatalogServer(inst, limits=lim)
    qid = srv.submit_query("null")     # auto-profiled by the threshold
    srv.run()
    assert srv.profile(qid).slow is False
    assert srv.slow_queries() == []


def test_limits_validate_slow_query_knobs():
    with pytest.raises(ValueError):
        ServerLimits(slow_query_threshold=-1.0)
    with pytest.raises(ValueError):
        ServerLimits(slow_query_log=0)


# --------------------------------------------------------------------------
# EXPLAIN through the server
# --------------------------------------------------------------------------


def test_server_explain_current_plan_and_candidate_program():
    inst = _csda_instance()
    srv = DatalogServer(inst)
    est = srv.explain()
    assert est.actuals                 # materialised IDB counts ride along
    assert "stratum 0" in srv.explain(text=True)
    # pre-flight a candidate program against this instance's EDB sizes
    cand = srv.explain(TC)
    assert cand.sizes.get("arc", 0) > 0
    assert cand.strata
    prom = srv.metrics_prometheus()
    assert "datalog_explain_requests_total 3" in prom


# --------------------------------------------------------------------------
# Prometheus exposition fixes (satellite 2)
# --------------------------------------------------------------------------


def test_prometheus_label_values_are_escaped():
    reg = MetricsRegistry()
    reg.counter(
        "odd_total", "with \\ and\nnewline",
        labels={"who": 'a"b\\c\nd'},
    ).inc()
    text = reg.to_prometheus()
    assert '{who="a\\"b\\\\c\\nd"}' in text
    assert "# HELP odd_total with \\\\ and\\nnewline" in text
    assert "\n\n" not in text          # escaped newlines never split lines


def test_prometheus_nonfinite_values_render_spec_spellings():
    reg = MetricsRegistry()
    reg.gauge("inf_gauge").set(float("inf"))
    reg.gauge("ninf_gauge").set(float("-inf"))
    reg.gauge("nan_gauge").set(float("nan"))
    text = reg.to_prometheus()
    assert "inf_gauge +Inf" in text
    assert "ninf_gauge -Inf" in text
    assert "nan_gauge NaN" in text


def test_histogram_inf_bucket_and_sum_count_consistency():
    reg = MetricsRegistry()
    h = reg.histogram("h_seconds", buckets=(1.0, 2.0))
    for v in (0.5, 1.5, 99.0):
        h.observe(v)
    text = reg.to_prometheus()
    assert 'h_seconds_bucket{le="+Inf"} 3' in text
    assert "h_seconds_count 3" in text
    assert "h_seconds_sum 101" in text


# --------------------------------------------------------------------------
# CI perf-regression gate (satellite 1 + tentpole d)
# --------------------------------------------------------------------------


def _record(**metrics):
    return {"git_rev": "x", "timestamp": "t", "sections": {"serve": metrics}}


def test_gate_direction_and_threshold():
    base = _record(q_p50=1.0, txn_speedup=2.0)
    # durations: up is bad
    assert gate(base, _record(q_p50=1.2, txn_speedup=2.0), 0.15)
    assert not gate(base, _record(q_p50=1.1, txn_speedup=2.0), 0.15)
    # speedups: down is bad
    assert higher_is_better("serve_txn_speedup")
    assert gate(base, _record(q_p50=1.0, txn_speedup=1.5), 0.15)
    assert not gate(base, _record(q_p50=1.0, txn_speedup=2.5), 0.15)
    # improvements never violate
    assert not gate(base, _record(q_p50=0.5, txn_speedup=4.0), 0.15)


def test_gate_cli_fails_on_synthetic_regression(tmp_path):
    base = tmp_path / "baseline.json"
    traj = tmp_path / "BENCH_serve.json"
    base.write_text(json.dumps([_record(q_p50=1.0)]))
    # 20% regression over a 15% threshold: exit 1
    traj.write_text(json.dumps([_record(q_p50=1.2)]))
    argv = [str(traj), "--gate", "--baseline", str(base)]
    assert gate_main(argv) == 1
    # identical record: exit 0
    traj.write_text(json.dumps([_record(q_p50=1.0)]))
    assert gate_main(argv) == 0
    # looser threshold passes the same regression
    traj.write_text(json.dumps([_record(q_p50=1.2)]))
    assert gate_main(argv + ["--threshold", "0.5"]) == 0


def test_gate_cli_noops_without_baseline_or_trajectory(tmp_path):
    traj = tmp_path / "BENCH_serve.json"
    missing = tmp_path / "no_baseline.json"
    # missing trajectory: informative exit 0
    assert gate_main([str(traj), "--gate", "--baseline", str(missing)]) == 0
    # trajectory present, baseline missing: informative exit 0
    traj.write_text(json.dumps([_record(q_p50=1.0)]))
    assert gate_main([str(traj), "--gate", "--baseline", str(missing)]) == 0
    # empty baseline array: still a no-op
    empty = tmp_path / "empty.json"
    empty.write_text("[]")
    assert gate_main([str(traj), "--gate", "--baseline", str(empty)]) == 0
