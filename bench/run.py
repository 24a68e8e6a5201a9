"""Run one cell of the benchmark that ``BENCHMARK.json`` describes, on this machine's chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run serves one deployment (``bench/configs/<config>.json`` and the
program, data and reference of ``bench/configs/<deployment>.py``) under
one traffic mix (``bench/traffic/<mix>.json``) through
``DatalogServer`` over ``MaterializedInstance`` with durability on:

1. it refuses to run unless JAX's devices are TPUs, as many as the cell
   asks for (exit 2, no result);
2. it keeps JAX's persistent compilation cache in ``<checkout>/.jax_cache``;
3. it makes the data from ``--seed`` and materializes the fixpoint;
4. it warms the cell's own shapes on the instance it then serves: it
   applies the writer's first transactions before the server attaches,
   until one builds no program or the mix's cap (``warmup.txns``) is
   reached, and asks one query per source the window will ask for; the
   window goes on from that state;
5. it serves the window for ``--seconds`` (with ``--trace 1`` under the
   profiler and the program's span tracer);
6. it compares every served answer, the final fixpoint and the durable log
   with the plain reference (``bench/check.py``);
7. it prints each compared number beside its limit on standard error, then
   one JSON line on standard output: ``correct``, ``attempted``,
   ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
   ``--trace 1`` its per-layer metrics, each read by
   ``bench/metrics/<metric>.py``), ``device``, with ``--trace 1``
   ``breakdown``, and last ``checks``.

``--fault <name>`` plants one of ``bench/faults.py``'s faults under the
timed path; the benchmark's own runs never pass it.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import check, faults, loadgen  # noqa: E402
from bench.clock import BuildClock  # noqa: E402
from bench.stats import percentile  # noqa: E402


# -- the benchmark's description ---------------------------------------------------


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(spec: dict, workload: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic mix) of ``workload``."""
    try:
        w = next(x for x in spec["workloads"] if x["name"] == workload)
    except StopIteration:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json") from None
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return w, config, mix


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: end-to-end ones without
    tracing, per-layer ones with it."""
    if not trace:
        return [m for m in spec["end_to_end"]
                if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in cell_metrics(spec, workload, False)}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload]) and m["moves"] in reported]


def deployment(config: dict):
    return importlib.import_module(f"bench.configs.{config['deployment']}")


# -- the chip ------------------------------------------------------------------------


def require_chips(n: int):
    """JAX's devices, which must be ``n`` or more TPUs; exits 2 otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"bench: needs {n} TPU chip(s); JAX has {len(devs)} "
              f"{devs[0].platform!r} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return devs


def enable_compile_cache() -> None:
    """The persistent cache at the checkout's fixed ``.jax_cache``."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_record() -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


# -- one run ---------------------------------------------------------------------------


def progress(msg: str) -> None:
    """One line of progress on standard error (the check lines come last)."""
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _profiler_options():
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def run_cell(w: dict, config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, fault: str | None = None,
             t_start: float | None = None) -> SimpleNamespace:
    """Set up, serve the window and check one cell; returns the raw run."""
    import jax

    from repro.obs.trace import TRACER
    from repro.persist import DurabilityConfig, DurabilityManager, DeltaWAL
    from repro.serve_datalog import DatalogServer, MaterializedInstance

    from bench.drive import Client, published_ready, replay

    t_start = time.perf_counter() if t_start is None else t_start
    dep = deployment(config)
    scratch = Path(tempfile.mkdtemp(prefix="bench-"))
    run = SimpleNamespace(spans=[], trace=None)
    try:
        with faults.planted(fault):
            setup = BuildClock().start()
            base = dep.base_facts(config)
            labels = loadgen.Labels(base, seed)
            facts = labels.facts(base)
            n = int(labels.ids.max()) + 1
            rel = config["write_relation"]
            due, sources = loadgen.reader_schedule(mix, labels, seconds)

            t = time.perf_counter()
            inst = MaterializedInstance(dep.PROGRAM, facts)
            published_ready(inst)
            run.materialize_s = time.perf_counter() - t
            progress(f"materialized in {run.materialize_s:.3f} s, "
                     f"{setup.compiles} programs ({setup.cache_hits} from the cache)")
            # warm-up: the writer's first transactions, applied before the
            # server attaches; the window goes on from them
            writer = loadgen.Writer(mix, base[rel], rel, labels)
            warmed = replay(inst, writer, mix["warmup"]["txns"], log=progress)
            run.warmup_txns = len(warmed)
            epoch0 = inst.epoch - len(warmed)
            backends = {r: inst.engine.stats.backend_used.get(r)
                        for r in config["required_modes"]}
            durable = DurabilityManager(DurabilityConfig(
                root=str(scratch / "durable"), fsync=config["guarantees"]["wal_fsync"]))
            srv = DatalogServer(inst, durability=durable)
            client = Client(srv, writer, config["query_relation"])
            client.warm_queries(np.unique(sources))
            setup.stop()
            run.setup_build = setup.record()
            run.setup_s = time.perf_counter() - t_start
            progress(f"set-up {run.setup_s:.3f} s, {setup.compiles} programs "
                     f"({setup.cache_hits} from the cache), "
                     f"{run.warmup_txns} warm-up transactions replayed")

            window = BuildClock().start()
            spans: list = []
            if trace:
                # each update group runs on a new writer thread, and the
                # tracer keys its buffers by thread ident, which the OS
                # reuses: take the spans after every transaction
                def harvest() -> None:
                    spans.extend(TRACER.spans())
                    TRACER.clear()

                client.on_txn_done = harvest
                TRACER.enable(max_spans_per_thread=1 << 20)
                jax.profiler.start_trace(str(scratch / "trace"),
                                         profiler_options=_profiler_options())
            t0, t1 = client.window(due, sources, seconds)
            run.window_close = t0 + seconds
            if trace:
                jax.profiler.stop_trace()
                TRACER.disable()
                harvest()
                run.spans = [
                    {"name": s.name, "args": dict(s.args), "start_ns": s.start_ns,
                     "dur_ns": s.dur_ns}
                    for s in spans
                    if s.dur_ns >= 0 and s.start_ns >= t0 * 1e9
                    and s.start_ns + s.dur_ns <= t1 * 1e9]
            window.stop()
            run.window_build = window.record()
            run.window_s = t1 - t0
            progress(f"window {run.window_s:.3f} s: {len(client.txns)} "
                     f"transactions, {len(client.queries)} queries, "
                     f"{window.compiles} programs built")
            run.device = device_record()

            # what the timed path produced, read back before it is freed
            client.query_epochs()
            run.queries = client.queries
            run.txns = client.txns
            final = {rel: inst.relation(rel) for rel in (*dep.EDB, dep.IDB)}
            final["epoch"] = inst.epoch
            srv.close()
            wal_floor = durable.last_snapshot_epoch
            with DeltaWAL(durable.wal.path, fsync="off") as log:
                wal = [(t.epoch, [(r.op, r.rel, r.rows) for r in t.ops])
                       for t in log.replay_txns() if t.epoch > wal_floor]
            del srv, inst, durable, client
            gc.collect()

        progress("window's results read back; checking against the reference")
        if trace:
            from bench import trace_reduce

            run.trace = trace_reduce.reduce(trace_reduce.load(str(scratch / "trace")))
        t = time.perf_counter()
        run.checks = judge(run, dep, facts, n, epoch0, warmed, final, wal,
                           wal_floor, backends, config)
        progress(f"checked in {time.perf_counter() - t:.3f} s")
        return run
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def judge(run, dep, facts, n, epoch0, warmed, final, wal, wal_floor, backends,
          config) -> dict[str, int]:
    """Every compared number of ``bench/check.py`` for this run."""
    from repro.serve_datalog import RequestError

    acked = warmed + [(x.ops, x.result.epoch) for x in run.txns
                      if x.result is not None
                      and not isinstance(x.result, RequestError)]
    answers = [(q.src, q.epoch, q.result) for q in run.queries
               if isinstance(q.result, np.ndarray)]
    never = (sum(q.result is None for q in run.queries)
             + sum(x.result is None for x in run.txns))
    path_off = sum(b != config["required_modes"][rel] for rel, b in backends.items())
    for x in run.txns:
        modes = set(getattr(x.result, "modes", {}).values())
        path_off += sum(m not in modes for m in config["required_modes"].values())
    return check.evaluate(dep, facts, n, epoch0, acked, answers, final, wal,
                          wal_floor, never, path_off)


def failures(run) -> tuple[int, int]:
    """(attempted, failed) requests of the window: a request fails when it
    gets an error or no result."""
    from repro.serve_datalog import RequestError

    reqs = [q.result for q in run.queries] + [x.result for x in run.txns]
    return len(reqs), sum(r is None or isinstance(r, RequestError) for r in reqs)


def end_to_end(run) -> dict[str, float | None]:
    """The end-to-end metrics of a run (host clock)."""
    from repro.serve_datalog import RequestError

    # a serving step that waits on the device can run past the close: a
    # transaction that becomes visible after it is not one of the window's
    visible = [x.visible - x.submitted for x in run.txns
               if x.visible is not None and x.visible <= run.window_close
               and not isinstance(x.result, RequestError)]
    lat = [q.answered - q.due for q in run.queries
           if isinstance(q.result, np.ndarray)]
    return {
        "setup_s": run.setup_s,
        "txn_visible_ms": sum(visible) / len(visible) * 1e3 if visible else None,
        "query_p50_ms": percentile(lat, 0.50) * 1e3 if lat else None,
        "query_p95_ms": percentile(lat, 0.95) * 1e3 if lat else None,
    }


def result(spec: dict, workload: str, run, trace: bool) -> dict:
    """The contract's result line of one run."""
    if trace:
        values = {m["name"]: importlib.import_module(
            f"bench.metrics.{m['name']}").read(run)
            for m in cell_metrics(spec, workload, True)}
    else:
        e2e = end_to_end(run)
        values = {m["name"]: e2e[m["name"]]
                  for m in cell_metrics(spec, workload, False)}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    attempted, failed = failures(run)
    device = dict(run.device)
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    out = {
        "correct": all(v <= check.LIMITS[k] for k, v in run.checks.items()),
        "attempted": attempted,
        "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items() if v is not None},
        "device": device,
    }
    if trace and run.trace is not None:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": check.LIMITS[k]}
                     for k, v in run.checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=faults.FAULTS, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    spec = load_spec()
    w, config, mix = cell(spec, args.workload)
    require_chips(w["chips"])
    enable_compile_cache()
    run = run_cell(w, config, mix, args.seed, args.seconds, bool(args.trace),
                   fault=args.fault, t_start=_T_START)
    out = result(spec, args.workload, run, bool(args.trace))
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
