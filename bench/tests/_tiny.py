"""A cell small enough for a CPU test run: ``tc-g5k-ingest`` on a 200-vertex
sparse Gn-p graph, 3 arcs per transaction, 200 queries/s, a short warm-up."""

from __future__ import annotations

from bench import run

WORKLOAD = "tc-g5k-ingest"
SEED = 2**31 + 7
SECONDS = 2.5


def cell():
    spec = run.load_spec()
    w, config, mix = run.cell(spec, WORKLOAD)
    config["dataset"].update(n=200, p=0.005)
    mix["writer"]["insert"] = 3
    mix["readers"]["rate_per_s"] = 200.0
    mix["warmup"].update(txns=4)
    return spec, w, config, mix


def run_tiny(trace: bool = False, fault: str | None = None):
    """The result line of one tiny run, with the chip check skipped."""
    spec, w, config, mix = cell()
    r = run.run_cell(w, config, mix, SEED, SECONDS, trace, fault=fault)
    return run.result(spec, WORKLOAD, r, trace), r
