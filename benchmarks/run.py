"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Sections:
  fig2   — optimization ablations (UIE/OOF/DSD/EOST/dense off)
  fig10  — TC/SG on Gn-p: PBME vs tuple backend (+ Pallas kernel path)
  fig12  — REACH/CC/SSSP scaling on RMAT graphs
  fig15  — program analyses (Andersen scaling, CSPA, CSDA)
  fig8   — device-count scale-up of sharded PBME (+ Table 4 CPU efficiency)
  serve  — incremental serving: update-batch latency vs. full recompute
  scenarios — hostile-traffic scenario harness: seeded arrival traces vs.
              admission control (p50/p99 sojourn + shed/exactness verdicts)
  roofline — three-term roofline per dry-run cell (needs results/dryrun.json)

The growing ``serve`` section takes a sub-section filter, e.g.

  python -m benchmarks.run serve --sections insert,warm-start

picking from insert / delete / query / concurrent / warm-start / txn / obs.
``scenarios`` reuses the same flag to pick scenarios, e.g.

  python -m benchmarks.run scenarios --sections steady,burst

``--bench-json PATH`` appends one perf-trajectory record (git rev,
``--timestamp``, section -> headline seconds) to PATH after the run and
prints the delta vs. the previous record — see ``benchmarks/trajectory.py``.

A section that raises prints a ``<section>_FAILED`` row and the run goes on
to the next section; the process then exits with status 1.  Compiled
programs persist across runs (``repro.compile_cache``).
"""

from __future__ import annotations

import functools
import os
import sys
import traceback


def _parse_args(
    argv: list[str],
) -> tuple[list[str], list[str] | None, str | None, str | None]:
    """Split section names from ``--sections`` / ``--bench-json`` / ``--timestamp``."""
    sections: list[str] = []
    serve_sections: list[str] | None = None
    bench_json: str | None = None
    timestamp: str | None = None

    def take_value(flag: str, i: int) -> tuple[str, int]:
        if i + 1 >= len(argv):
            raise SystemExit(f"{flag} needs a value")
        return argv[i + 1], i + 2

    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg == "--sections":
            val, i = take_value(arg, i)
            serve_sections = [s for s in val.split(",") if s]
        elif arg.startswith("--sections="):
            serve_sections = [s for s in arg.split("=", 1)[1].split(",") if s]
            i += 1
        elif arg == "--bench-json":
            bench_json, i = take_value(arg, i)
        elif arg.startswith("--bench-json="):
            bench_json = arg.split("=", 1)[1]
            i += 1
        elif arg == "--timestamp":
            timestamp, i = take_value(arg, i)
        elif arg.startswith("--timestamp="):
            timestamp = arg.split("=", 1)[1]
            i += 1
        else:
            sections.append(arg)
            i += 1
    return sections, serve_sections, bench_json, timestamp


def main() -> None:
    sections, serve_sections, bench_json, timestamp = _parse_args(sys.argv[1:])
    sections = sections or [
        "fig2",
        "fig10",
        "fig12",
        "fig15",
        "fig8",
        "serve",
        "roofline",
    ]
    from benchmarks import common
    from repro import compile_cache

    compile_cache.enable()
    failed: list[str] = []
    section_rows: dict[str, dict[str, float]] = {}
    print("name,us_per_call,derived")
    for sec in sections:
        mark = len(common.ROWS)
        try:
            if sec == "fig2":
                from benchmarks.bench_optimizations import run as r
            elif sec == "fig10":
                from benchmarks.bench_tc_sg import run as r
            elif sec == "fig12":
                from benchmarks.bench_graph_analytics import run as r
            elif sec == "fig15":
                from benchmarks.bench_program_analysis import run as r
            elif sec == "fig8":
                from benchmarks.bench_scaleup import run as r
            elif sec == "serve":
                from benchmarks.bench_serve_datalog import run as r

                if serve_sections is not None:
                    r = functools.partial(r, sections=serve_sections)
            elif sec == "scenarios":
                from benchmarks.bench_scenarios import run as r

                if serve_sections is not None:
                    r = functools.partial(r, sections=serve_sections)
            elif sec == "roofline":
                if not os.path.exists("results/dryrun.json"):
                    print(f"{sec}_skipped,0,no results/dryrun.json (run dryrun first)")
                    continue
                from benchmarks.roofline import run as r
            else:
                print(f"{sec}_unknown,0,")
                continue
            r()
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            print(f"{sec}_FAILED,0,{type(e).__name__}")
            failed.append(sec)
        rows = common.ROWS[mark:]
        if rows:
            # last value wins on duplicate names within a section
            section_rows[sec] = {name: secs for name, secs, _ in rows}

    if bench_json:
        from benchmarks import trajectory

        record = trajectory.make_record(section_rows, timestamp=timestamp)
        records = trajectory.append_record(bench_json, record)
        print(f"# trajectory: appended record {len(records)} to {bench_json}",
              file=sys.stderr)
        if len(records) >= 2:
            print(trajectory.format_compare(records[-2], records[-1]),
                  file=sys.stderr)
    if failed:
        sys.exit(f"failed sections: {', '.join(failed)}")


if __name__ == "__main__":
    main()
