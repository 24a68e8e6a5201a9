"""``chip_smoke.py``'s phases and checks, rehearsed on the CPU at a tiny size.

The script's ``main`` refuses any backend but a TPU, so these tests load the
script by path and call its phase and check functions directly: both
serving phases end to end, the kernel check in interpret mode, and the
failures (a failed request, a wrong answer, a checkpoint error, a skipped
WAL record) that must each fail the run.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.serve_datalog import MaterializedInstance, RequestError

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_require_tpu_refuses_cpu(smoke):
    with pytest.raises(SystemExit) as e:
        smoke.require_tpu()
    assert e.value.code == 2


def test_kernel_phase_matches_ref(smoke):
    rec = smoke.kernel_phase(200, 0.02, 0)
    assert rec["equal_to_bitmm_ref"] is True


def test_tc_phase_serves_exact_answers(smoke, tmp_path):
    rec = smoke.tc_phase(200, 0.02, 0, tmp_path)
    assert rec["backend"] == "bitmatrix"
    assert rec["queries"] == smoke.N_QUERIES
    assert rec["rows_matched"] > 0
    assert (tmp_path / "tc_state").is_dir()


def test_andersen_phase_serves_exact_answers(smoke, tmp_path):
    rec = smoke.andersen_phase(1, 0, tmp_path)
    assert rec["pointsTo"] == rec["fixpoint_rows"] > 0
    assert rec["rows_matched"] > 0
    assert "dred" in rec["txn_modes"]
    assert rec["txn_compiles"] >= rec["txn_cache_hits"] >= 0
    assert "phase_peak_bytes" in rec


def test_failed_query_fails_the_phase(smoke, tmp_path, monkeypatch):
    def broken(self, rel, **kw):
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(MaterializedInstance, "query", broken)
    with pytest.raises(smoke.SmokeFailure, match="failed"):
        smoke.tc_phase(60, 0.05, 0, tmp_path, n_changes=8)


def test_reference_mismatch_fails_the_phase(smoke, tmp_path, monkeypatch):
    true_reachable = smoke.reachable

    def off_by_one(edges, n, src):
        return np.append(true_reachable(edges, n, src), n)

    monkeypatch.setattr(smoke, "reachable", off_by_one)
    with pytest.raises(smoke.SmokeFailure, match="reference has"):
        smoke.tc_phase(60, 0.05, 0, tmp_path, n_changes=8)


def test_check_served_rejects_errors_and_mismatches(smoke):
    rows = np.array([[3, 1], [3, 4]], np.int32)
    expected = {3: np.array([1, 4])}
    assert smoke.check_served({7: rows}, {3: 7}, expected) == 2
    with pytest.raises(smoke.SmokeFailure, match="failed"):
        smoke.check_served({7: RequestError(7, "boom")}, {3: 7}, expected)
    with pytest.raises(smoke.SmokeFailure, match="reference has"):
        smoke.check_served({7: rows[:1]}, {3: 7}, expected)
    with pytest.raises(smoke.SmokeFailure, match="another source"):
        smoke.check_served({7: rows[:, ::-1]}, {3: 7}, expected)
    with pytest.raises(smoke.SmokeFailure, match="failed"):
        smoke.check_txn({1: RequestError(1, "boom")}, 1)


def test_durability_checks_reject_errors(smoke):
    class Srv:
        checkpoint_errors = ["OSError: disk full"]

    class Restored:
        restore_stats = {"skipped_records": 1}

    with pytest.raises(smoke.SmokeFailure, match="checkpoint"):
        smoke.check_durability(Srv())
    with pytest.raises(smoke.SmokeFailure, match="skipped"):
        smoke.check_restore(Restored())


def test_clock_records_program_building(smoke):
    import jax
    import jax.numpy as jnp

    with smoke.Clock() as clk:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    rec = clk.record("part")
    assert rec["part_compiles"] >= 1
    assert 0 < rec["part_compile_seconds"] <= rec["part_seconds"]
    assert rec["part_trace_seconds"] > 0
    assert rec["part_cache_hits"] <= rec["part_compiles"]


def test_memory_record_attributes_only_a_raised_peak(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "device_memory", lambda: (10, 500))
    assert smoke.memory_record((5, 400))["phase_peak_bytes"] == 500
    own = smoke.memory_record((5, 500))
    assert own["phase_peak_bytes"] is None
    assert own["peak_bytes_in_use"] == 500 and own["bytes_in_use"] == 10
    monkeypatch.setattr(smoke, "device_memory", lambda: None)
    assert smoke.memory_record(None)["peak_bytes_in_use"] is None
