"""A whole run of the harness at a tiny size on the CPU, and its refusal to
measure anything but a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests._tiny import WORKLOAD, run_tiny

ROOT = Path(__file__).resolve().parents[2]
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def untraced():
    return run_tiny(trace=False)


def test_a_sound_run_is_correct(untraced):
    out, run = untraced
    assert out["correct"] is True
    assert all(c["value"] == 0 == c["limit"] for c in out["checks"].values())
    assert out["failed"] == 0 and out["attempted"] == len(run.queries) + len(run.txns)
    assert len(run.txns) >= 2 and len(run.queries) >= 100


def test_the_result_line_has_the_contract_keys_and_checks_last(untraced):
    from bench import run

    out, _run = untraced
    assert list(out) == CONTRACT_KEYS + ["checks"]
    want = {m["name"] for m in run.cell_metrics(run.load_spec(), WORKLOAD, False)}
    assert set(out["metrics"]) == want and {"setup_s", "txn_visible_ms"} <= want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    json.dumps(out)


def test_a_traced_run_reports_the_layers_and_a_breakdown():
    out, run = run_tiny(trace=True)
    assert out["correct"] is True
    assert list(out)[:5] == CONTRACT_KEYS and list(out)[-1] == "checks"
    assert {"materialize_s", "setup_build_s", "window_programs", "txn_apply_ms",
            "wal_fsync_ms", "pbme_update_ms", "query_service_ms",
            "gen_lag_p95_ms"} <= set(out["metrics"])
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] == pytest.approx(run.window_s, rel=0.05)


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOAD, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
