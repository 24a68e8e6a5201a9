"""BENCHMARK.json against the benchmark's contract, and the files it names."""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"] for m in SPEC["end_to_end"]}
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("group,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_the_contract_keys(group, keys):
    for entry in SPEC[group]:
        assert keys <= set(entry) <= keys | {"workloads"}, entry["name"]
        if group in ("configs", "workloads"):
            assert "workloads" not in entry


def test_names_units_and_lines():
    names = [e["name"] for g in ("configs", "workloads", "end_to_end", "per_layer")
             for e in SPEC[g]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for c in SPEC["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in E2E
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")


def test_every_moves_names_an_end_to_end_metric_of_its_cells():
    for m in SPEC["per_layer"]:
        assert m["moves"] in E2E, m["name"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS, (m["name"], cell)
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all("\n" not in layer and len(layer) <= 200 for layer in layers)


def test_cells_find_their_files_by_name():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = set()
    pairs = set()
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        entry = configs[w["config"]]
        used.add(entry["name"])
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert entry["file"].startswith("bench/configs/")
        assert cfg["name"] == entry["name"]
        assert set(cfg["reduced"]) == set(entry["reduced"])
        dep = importlib.import_module(f"bench.configs.{cfg['deployment']}")
        assert cfg["write_relation"] in dep.EDB and cfg["query_relation"] == dep.IDB
        mix = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert {"writer", "readers", "warmup"} <= set(mix)
    assert used == set(configs)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    from bench import run

    for w in CELLS:
        e2e = {m["name"] for m in run.cell_metrics(SPEC, w, False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.cell_metrics(SPEC, w, True)


def test_peaks_table():
    from bench.peaks import peaks

    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
