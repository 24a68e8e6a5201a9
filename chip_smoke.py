"""Serve two real-size Datalog deployments on one TPU chip and check every answer.

    python chip_smoke.py [--seed 0] [--out results/chip_smoke/]
                         [--andersen-scale 1]

Runs on a TPU and nowhere else: with no TPU among JAX's devices it exits
with status 2 before any phase runs.  Phases, each through the user entry
points (``MaterializedInstance`` + ``DatalogServer`` with durability),
in this order:

* ``tuple-andersen`` — Andersen points-to over ``andersen_facts`` at
  ``--andersen-scale`` on the tuple path (sort-merge joins, dedup, set
  difference); one transaction of 32 ``assign`` inserts and 32
  retractions; 16 point queries ``pointsTo(src=y)``; restore and re-query.
* ``bitmm-kernel`` — one fused Pallas PBME iteration (Mosaic-compiled) on
  the G5K arc matrix, compared bit for bit with the jnp ``bitmm_ref``.
* ``pbme-tc-g5k`` — transitive closure over the paper's Gn-p graph G5K
  (n = 5000, p = 0.001) on the bit-matrix backend; one transaction of 128
  arc inserts and 128 arc retractions; 16 point queries ``tc(src=s)``;
  close, restore from the durability directory, and the same 16 queries.

Every served answer is compared exactly with a plain reference that does
not use ``repro``: numpy BFS for TC, a dense boolean-matrix fixpoint in
plain ``jnp`` for Andersen.  A failed request, a checkpoint error, a WAL
record skipped on restore or a mismatch raises, and the script exits
non-zero.  Each phase prints one JSON line: wall seconds of set-up,
transaction, steady queries and restore kept apart, each with the XLA
programs built inside it and the seconds spent building them; device
memory; rows matched.  The last line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro import compile_cache  # noqa: E402
from repro.configs.datalog_workloads import ANDERSEN, TC  # noqa: E402
from repro.core.bitmatrix import bitmm_ref, edges_to_bitmatrix  # noqa: E402
from repro.data.graphs import gnp_graph  # noqa: E402
from repro.data.program_facts import andersen_facts  # noqa: E402
from repro.kernels.ops import bitmm_fused_delta  # noqa: E402
from repro.serve_datalog import (  # noqa: E402
    DatalogServer,
    MaterializedInstance,
    RequestError,
)

N_QUERIES = 16
# andersen_facts dataset served by the tuple-andersen phase.  The paper's
# largest is 7, but on one v5e the tuple path did not materialize scale 4
# within 560 s, and with an empty compile cache a whole run at scale 2 went
# past the smoke's 1200 s limit: its Andersen phase alone took 763 s, 96 %
# of it compiling (PERF.md).
ANDERSEN_SCALE = 1


class SmokeFailure(AssertionError):
    """A served answer, a request or the durability round-trip went wrong."""


def require_tpu() -> jax.Device:
    """The first device, which must be a TPU (no phase runs on a fallback)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        raise SystemExit(2)
    return dev


# -- measurement helpers -----------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def device_memory() -> tuple[int, int] | None:
    """(bytes_in_use, peak_bytes_in_use) of the first device, where the
    backend reports them; the peak is the process's, not resettable."""
    stats = jax.devices()[0].memory_stats()
    if stats is None:
        return None
    return int(stats.get("bytes_in_use", 0)), int(stats.get("peak_bytes_in_use", 0))


def memory_record(before: tuple[int, int] | None) -> dict:
    """Device memory at the end of a phase that began with ``before``.

    ``phase_peak_bytes`` is the phase's own peak when the phase raised the
    process peak, and None when it stayed within an earlier phase's peak.
    """
    after = device_memory()
    if after is None:
        return {"bytes_in_use": None, "peak_bytes_in_use": None,
                "phase_peak_bytes": None}
    rose = before is None or after[1] > before[1]
    return {"bytes_in_use": after[0], "peak_bytes_in_use": after[1],
            "phase_peak_bytes": after[1] if rose else None}


class Clock:
    """Wall seconds of one measured part and the program building inside it.

    ``compiles`` counts XLA executables built, compiled or loaded from the
    persistent cache (``cache_hits`` of them loaded); ``compile_seconds``
    sums their build time, cache loads included; ``trace_seconds`` sums
    tracing to a jaxpr and lowering to MLIR (nested traces overlap).
    """

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_seconds += secs
        elif event in _TRACE_EVENTS:
            self.trace_seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def record(self, part: str) -> dict:
        return {f"{part}_seconds": self.seconds,
                f"{part}_compiles": self.compiles,
                f"{part}_compile_seconds": self.compile_seconds,
                f"{part}_cache_hits": self.cache_hits,
                f"{part}_trace_seconds": self.trace_seconds}

    def __enter__(self):
        self.compiles = self.cache_hits = 0
        self.compile_seconds = self.trace_seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


# -- plain references (no repro code) ----------------------------------------


def reachable(edges: np.ndarray, n: int, src: int) -> np.ndarray:
    """Sorted ids y with a path of length ≥ 1 from ``src`` (numpy BFS)."""
    order = np.argsort(edges[:, 0], kind="stable")
    dst = edges[order, 1]
    start = np.searchsorted(edges[order, 0], np.arange(n + 1))
    seen = np.zeros(n, bool)
    frontier = np.array([src])
    while frontier.size:
        nxt = np.concatenate([dst[start[v]:start[v + 1]] for v in frontier])
        nxt = np.unique(nxt[~seen[nxt]])
        seen[nxt] = True
        frontier = nxt
    return np.flatnonzero(seen)


def andersen_reference(facts: dict[str, np.ndarray], n: int) -> np.ndarray:
    """Dense boolean fixpoint of the four Andersen rules in plain jnp:

        P ← addressOf ∪ assign·P ∪ load·P·P ∪ Pᵀ·store·P

    Returns the fixpoint as a host bool[n, n] with P[y, x] = pointsTo(y, x).
    {0,1} bf16 products accumulate exactly in f32.
    """

    def dense(rows):
        m = np.zeros((n, n), np.float32)
        m[rows[:, 0], rows[:, 1]] = 1.0
        return jnp.asarray(m, jnp.bfloat16)

    def mm(a, b):
        c = jnp.dot(a, b, preferred_element_type=jnp.float32)
        return (c > 0).astype(jnp.bfloat16)

    @jax.jit
    def step(p, assign, load, store):
        nxt = jnp.maximum(p, mm(assign, p))
        nxt = jnp.maximum(nxt, mm(mm(load, p), p))
        nxt = jnp.maximum(nxt, mm(mm(p.T, store), p))
        return nxt, jnp.any(nxt != p)

    p = dense(facts["addressOf"])
    assign, load, store = (dense(facts[k]) for k in ("assign", "load", "store"))
    changed = True
    while changed:
        p, changed = step(p, assign, load, store)
        changed = bool(changed)
    return np.asarray(p) > 0


# -- checks -------------------------------------------------------------------


def check_served(
    results: dict, rids: dict[int, int], expected: dict[int, np.ndarray]
) -> int:
    """Compare served point-query answers with the reference.

    ``rids`` maps a query's source id to its request id; ``expected`` maps
    the source to its sorted reference answer column.  Raises
    :class:`SmokeFailure` on a failed request or any difference; returns
    the number of rows matched.
    """
    matched = 0
    for src, rid in rids.items():
        got = results.get(rid)
        if isinstance(got, RequestError) or not isinstance(got, np.ndarray):
            raise SmokeFailure(f"query src={src} (rid {rid}) failed: {got!r}")
        if got.size and not (got[:, 0] == src).all():
            raise SmokeFailure(f"query src={src} returned rows of another source")
        col = np.sort(got[:, 1]) if got.size else np.zeros(0, np.int64)
        want = expected[src]
        if col.shape != want.shape or not np.array_equal(col, want):
            raise SmokeFailure(
                f"query src={src}: {col.size} rows served, reference has "
                f"{want.size}"
            )
        matched += int(col.size)
    return matched


def check_txn(results: dict, rid: int):
    got = results.get(rid)
    if isinstance(got, RequestError) or got is None:
        raise SmokeFailure(f"transaction rid {rid} failed: {got!r}")
    return got


def check_durability(srv: DatalogServer) -> None:
    if srv.checkpoint_errors:
        raise SmokeFailure(f"checkpoint errors: {srv.checkpoint_errors}")


def check_restore(inst: MaterializedInstance) -> None:
    skipped = inst.restore_stats["skipped_records"]
    if skipped:
        raise SmokeFailure(f"restore skipped {skipped} WAL records")


# -- shared serving round ------------------------------------------------------


def _new_pairs(rng, ids: np.ndarray, existing: np.ndarray, k: int) -> np.ndarray:
    """``k`` distinct non-self pairs over ``ids`` that are not in ``existing``."""
    have = {tuple(r) for r in existing.tolist()}
    out: list[tuple[int, int]] = []
    while len(out) < k:
        a, b = (int(v) for v in rng.choice(ids, 2))
        if a != b and (a, b) not in have:
            have.add((a, b))
            out.append((a, b))
    return np.asarray(out, np.int32)


def _query_all(srv: DatalogServer, rel: str, sources) -> tuple[dict, dict]:
    rids = {int(s): srv.submit_query(rel, src=int(s)) for s in sources}
    return srv.run(), rids


def serve_round(
    program: str,
    facts: dict[str, np.ndarray],
    rel: str,
    edb: str,
    n_changes: int,
    seed: int,
    state: Path,
    reference,
) -> dict:
    """Materialize, transact, query, restore, re-query; every answer checked.

    ``reference(post_facts, sources)`` returns {source: sorted answer}.
    """
    rng = np.random.default_rng(seed + 1)
    if state.exists():
        shutil.rmtree(state)
    mem0 = device_memory()
    with Clock() as setup:
        inst = MaterializedInstance(program, facts)
        srv = DatalogServer(inst, durability=str(state))
        domain = inst.vstore.domain
        sources = rng.choice(domain, N_QUERIES, replace=False)
        first, first_rids = _query_all(srv, rel, sources[:1])
    matched = check_served(first, first_rids, reference(facts, sources[:1]))
    out: dict = {"domain": int(domain), **setup.record("setup")}
    out["fixpoint_rows"] = int(inst.vstore.handles[rel].count)
    used = inst.engine.stats.backend_used.get(rel)
    out["backend"] = used if isinstance(used, str) else type(used).__name__

    ids = np.unique(np.concatenate([f.ravel() for f in facts.values()]))
    base = facts[edb]
    ins = _new_pairs(rng, ids, base, n_changes)
    ret = base[rng.choice(len(base), n_changes, replace=False)]
    with Clock() as txn:
        rid = srv.transaction().insert(edb, ins).retract(edb, ret).submit()
        stats = check_txn(srv.run(), rid)
    out.update(txn.record("txn"), txn_modes=sorted(set(stats.modes.values())))

    keep = np.ones(len(base), bool)
    gone = {tuple(r) for r in ret.tolist()}
    keep[[i for i, r in enumerate(base.tolist()) if tuple(r) in gone]] = False
    post = dict(facts)
    post[edb] = np.concatenate([base[keep], ins])
    expected = reference(post, sources)

    with Clock() as steady:
        results, rids = _query_all(srv, rel, sources)
    matched += check_served(results, rids, expected)
    check_durability(srv)
    srv.close()
    check_durability(srv)
    out.update(steady.record("steady"), queries=N_QUERIES)

    with Clock() as rest:
        restored = MaterializedInstance.restore(str(state))
        check_restore(restored)
        rsrv = DatalogServer(restored)
        results, rids = _query_all(rsrv, rel, sources)
    matched += check_served(results, rids, expected)
    rsrv.close()
    out.update(rest.record("restore"), rows_matched=matched,
               **memory_record(mem0))
    return out


# -- phases ---------------------------------------------------------------------


def tc_phase(n: int, p: float, seed: int, out_dir: Path,
             n_changes: int = 128) -> dict:
    """PBME transitive closure over Gn-p(n, p) served end to end."""
    edges = gnp_graph(n, p=p, seed=seed)

    def reference(post, sources):
        return {int(s): reachable(post["arc"], n, int(s)) for s in sources}

    res = serve_round(TC.program, {"arc": edges}, "tc", "arc", n_changes,
                      seed, out_dir / "tc_state", reference)
    if res["backend"] != "bitmatrix":
        raise SmokeFailure(f"tc stratum ran on {res['backend']}, not bitmatrix")
    return {"phase": "pbme-tc-g5k", "n": n, "p": p, "arcs": int(len(edges)),
            **res}


def andersen_phase(scale: int, seed: int, out_dir: Path,
                   n_changes: int = 32) -> dict:
    """Andersen points-to on the tuple path served end to end."""
    facts, n_vars = andersen_facts(scale, seed)

    def reference(post, sources):
        n = max(int(f.max()) + 1 for f in post.values() if f.size)
        fix = andersen_reference(post, n)
        return {int(s): np.flatnonzero(fix[int(s)]) for s in sources}

    res = serve_round(ANDERSEN.program, facts, "pointsTo", "assign", n_changes,
                      seed, out_dir / "andersen_state", reference)
    return {"phase": "tuple-andersen", "scale": scale, "n_vars": n_vars,
            "edb_facts": int(sum(len(f) for f in facts.values())),
            "pointsTo": res["fixpoint_rows"], **res}


def kernel_phase(n: int, p: float, seed: int) -> dict:
    """One fused Pallas PBME iteration on Gn-p's arc matrix vs ``bitmm_ref``."""
    mem0 = device_memory()
    arc = edges_to_bitmatrix(gnp_graph(n, p=p, seed=seed), n)
    with Clock() as clk:
        delta, m_new = bitmm_fused_delta(arc, arc, arc)
        jax.block_until_ready((delta, m_new))
    new = bitmm_ref(arc, arc, n)
    want_delta = new & ~arc
    want_m = arc | want_delta
    equal = bool(jnp.array_equal(delta, want_delta)) and bool(
        jnp.array_equal(m_new, want_m)
    )
    if not equal:
        raise SmokeFailure("fused bitmm kernel differs from bitmm_ref")
    return {"phase": "bitmm-kernel", "n": n, "equal_to_bitmm_ref": equal,
            "words": int(arc.shape[1]), **clk.record("first_call"),
            **memory_record(mem0)}


def emit(record: dict, log) -> None:
    line = json.dumps(record)
    print(line, flush=True)
    log.write(line + "\n")
    log.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "results" / "chip_smoke"))
    ap.add_argument("--andersen-scale", type=int, default=ANDERSEN_SCALE,
                    help="andersen_facts dataset of the tuple-andersen phase")
    args = ap.parse_args(argv)

    dev = require_tpu()
    compile_cache.enable()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with open(out / "chip_smoke.jsonl", "w") as log:
        # Andersen first: its device memory is then its own (the process
        # peak cannot be reset, and TC's is far larger).
        emit(andersen_phase(args.andersen_scale, args.seed, out), log)
        emit(kernel_phase(5000, 0.001, args.seed), log)
        emit(tc_phase(5000, 0.001, args.seed, out), log)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
