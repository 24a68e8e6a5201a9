"""Compiled-plan cache: parse/stratify once, keep jitted executables warm.

Serving traffic repeats the same program over and over (every update batch
and every query hits the same stratification, the same delta-variant groups,
the same jitted relational kernels at the same capacity buckets).  Adaptive
Recursive Query Optimization (arXiv 2312.04282) motivates reusing plans
across repeated executions; here the plan is

* the *logical* plan — parsed :class:`Program`, :class:`Stratification` and
  per-stratum semi-naïve variant groups, cached by program fingerprint in an
  LRU; and
* the *physical* plan — the jitted executables behind ``_sort_pad`` /
  ``_dedup_sorted`` / ``_merge_sorted`` / query selection.  JAX keys its
  executable cache by operand shape, and every shape in this codebase is a
  power-of-two capacity bucket, so :meth:`PlanCache.warm` pre-traces the hot
  kernels per (program fingerprint, capacity bucket, domain) — steady-state
  requests at warmed buckets skip tracing; a shape first reached as tables
  grow still traces once on first touch.
"""

from __future__ import annotations

import functools
import hashlib
from collections import OrderedDict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import AnalysisConfig, AnalysisReport, analyze_program
from repro.core.analyzer import Stratification, analyze
from repro.core.ast import Program
from repro.core.relation import _dedup_sorted, _merge_sorted, _sort_pad, pad_delta
from repro.core.seminaive import RuleVariant, delta_variants
from repro.obs.trace import TRACER as _TRACE
from repro.relational.sort import SENTINEL
from repro.serve_datalog.errors import RequestError

# Admission default: full error + lint passes, semantics-preserving
# rewrites on, no PBME/demand explainers (those re-run whole-program
# probes; ``lint`` requests get them instead).
ADMISSION_CONFIG = AnalysisConfig(explain_pbme=False, explain_demand=False)


def fingerprint(program: Program | str) -> str:
    """Stable fingerprint of a program's canonical (parsed-AST) form.

    Source text is parsed first so the same program fingerprints identically
    whether passed as text or as a :class:`Program` — whitespace, rule
    formatting, and argument form all normalize away.
    """
    if isinstance(program, str):
        from repro.core.parser import parse

        program = parse(program)
    return hashlib.sha1(repr(program).encode()).hexdigest()[:16]


@dataclass
class CompiledPlan:
    """Logical plan: everything derivable from the program text alone.

    ``program`` (and ``fingerprint``) are the analyzer's *rewritten*
    program — the one actually planned and evaluated.  Because every
    rewrite is idempotent, re-admitting a rewritten program (e.g. a
    snapshot manifest's ``program_source`` on warm start) maps to the
    same fingerprint.  ``report`` carries the admission diagnostics
    (``None`` when analysis was bypassed).
    """

    fingerprint: str
    program: Program
    strat: Stratification
    delta_groups: list[dict[str, list[RuleVariant]]] = field(repr=False)
    report: AnalysisReport | None = field(default=None, repr=False)

    def groups_for(self, stratum_index: int) -> dict[str, list[RuleVariant]]:
        return self.delta_groups[stratum_index]

    def explain(
        self,
        sizes: dict[str, float] | None = None,
        domain: int = 0,
        modes: dict[int, str] | None = None,
        actuals: dict[str, int] | None = None,
    ):
        """EXPLAIN this plan: per-rule/per-stratum cost and cardinality
        estimates (:class:`repro.obs.explain.PlanEstimate`).

        ``sizes`` maps relation → row count (EDB actuals; unknown relations
        default to ``domain``); ``modes`` maps stratum index → predicted
        evaluation mode.  Pure — touches no device state, so it is safe at
        admission time before any data exists.
        """
        from repro.obs.explain import estimate_plan

        return estimate_plan(
            self, sizes=sizes, domain=domain, modes=modes, actuals=actuals
        )


@functools.partial(jax.jit, static_argnames=("mask",))
def _select_rows(rows: jax.Array, lov: jax.Array, hiv: jax.Array, mask: tuple):
    """Point/range selection over a padded tuple table.

    ``mask[i]`` marks column ``i`` as constrained to ``[lov[i], hiv[i]]``
    (point queries have ``lov == hiv``).  The mask is static so each bound
    pattern compiles once per capacity bucket; matches are compacted to the
    front preserving sort order.
    """
    valid = rows[:, 0] != SENTINEL
    for i, constrained in enumerate(mask):
        if constrained:
            valid &= (rows[:, i] >= lov[i]) & (rows[:, i] <= hiv[i])
    kept = jnp.where(valid[:, None], rows, SENTINEL)
    order = jnp.argsort(~valid, stable=True)
    return kept[order], valid.sum()


class PlanCache:
    """LRU of :class:`CompiledPlan` + warmed-executable bookkeeping.

    One :func:`default_cache` instance is shared process-wide so every
    instance/server reuses warm executables; pass a private ``PlanCache`` to
    isolate tenants.  ``select`` is safe to call from reader threads while a
    writer updates the instance — it touches only jitted pure functions and
    the (GIL-guarded) warmth bookkeeping.
    """

    def __init__(self, capacity: int = 32):
        self.capacity = capacity
        self._plans: OrderedDict[str, CompiledPlan] = OrderedDict()
        # demand-specialized plans, keyed by (source fingerprint, adornment,
        # analysis + demand config fingerprints) — see get_demand
        self._demand: OrderedDict[str, tuple] = OrderedDict()
        # (fp, bucket, arity, domain) — domain is a static argname of every
        # kernel traced below, so warmth is per-domain too
        self._warmed: set[tuple[str, int, int, int]] = set()
        self.hits = 0
        self.misses = 0

    # -- logical plans -----------------------------------------------------

    def get(
        self,
        program: Program | str,
        analysis: AnalysisConfig | None = ADMISSION_CONFIG,
    ) -> CompiledPlan:
        """Admit ``program``: analyze, rewrite, stratify, cache.

        The static analyzer runs on every cache miss; a program with any
        ``DL0xx`` error diagnostic is rejected with a :class:`RequestError`
        carrying the full diagnostic list and is never cached.  What gets
        planned (and fingerprinted) is the analyzer's rewritten program,
        so the LRU key pairs the *source* fingerprint with the analysis
        config's — two admissions under different rewrite configs never
        share a slot.  ``analysis=None`` bypasses the analyzer (legacy
        validate-only admission); plain ``ValueError`` from validation
        still surfaces as a structured :class:`RequestError`.
        """
        with _TRACE.span("plan_cache.get", "serve") as sp:
            if isinstance(program, str):
                from repro.core.parser import DatalogSyntaxError, parse

                try:
                    program = parse(program, validate=False)
                except DatalogSyntaxError as e:
                    raise RequestError(
                        -1, f"program rejected: {e.args[0]}"
                    ) from e
            source_fp = fingerprint(program)
            key = f"{source_fp}:{analysis.fingerprint() if analysis else 'raw'}"
            if key in self._plans:
                self.hits += 1
                self._plans.move_to_end(key)
                sp.set(fingerprint=self._plans[key].fingerprint, hit=True)
                return self._plans[key]
            self.misses += 1
            report: AnalysisReport | None = None
            if analysis is not None:
                report = analyze_program(program, analysis)
                if not report.ok:
                    first = report.errors[0]
                    raise RequestError(
                        -1,
                        f"program rejected by static analysis "
                        f"({len(report.errors)} error(s), first: "
                        f"{first.render()})",
                        diagnostics=report.diagnostics,
                    )
                program = report.rewritten
            fp = fingerprint(program)
            sp.set(fingerprint=fp, hit=False)
            try:
                strat = analyze(program)
            except ValueError as e:
                # unreachable when the analyzer ran (it mirrors these
                # checks), load-bearing for the bypass path
                raise RequestError(-1, f"program rejected: {e}") from e
            plan = CompiledPlan(
                fp,
                program,
                strat,
                [delta_variants(s) for s in strat.strata],
                report=report,
            )
            self._plans[key] = plan
            while len(self._plans) > self.capacity:
                self._plans.popitem(last=False)
            return plan

    def get_demand(
        self,
        program: Program | str,
        query_pred: str,
        pattern: str,
        analysis: AnalysisConfig | None = ADMISSION_CONFIG,
        demand_config=None,
        *,
        sizes: dict[str, float] | None = None,
        domain: int = 0,
    ) -> tuple[CompiledPlan, "object"]:
        """Admit a demand-specialized plan for ``query_pred^pattern``.

        Returns ``(plan, transform)``.  Specialized plans are keyed by
        ``(source fingerprint, adornment, analysis config, demand
        config)`` so the same program specialized for different binding
        patterns — or under different SIP strategies — never shares a
        slot.  When the transform *falls back* (``transform.ok`` is
        False: unstratifiable, unprofitable, unseedable — a coded
        ``DL4xx`` info diagnostic, never an error) the returned plan is
        the ordinary :meth:`get` plan of the unspecialized program.
        ``sizes``/``domain`` feed the profitability estimate and are
        *not* part of the key: profitability is decided at first
        admission and revisited only when the entry is evicted.
        """
        from repro.analysis.demand import DEFAULT_DEMAND, demand_transform

        dconf = demand_config if demand_config is not None else DEFAULT_DEMAND
        base = self.get(program, analysis=analysis)
        key = (
            f"{base.fingerprint}:{query_pred}^{pattern}"
            f":{analysis.fingerprint() if analysis else 'raw'}"
            f":{dconf.fingerprint()}"
        )
        if key in self._demand:
            self.hits += 1
            self._demand.move_to_end(key)
            return self._demand[key]
        self.misses += 1
        with _TRACE.span(
            "plan_cache.get_demand", "serve",
            query=f"{query_pred}^{pattern}",
        ) as sp:
            transform = demand_transform(
                base.program, query_pred, pattern, dconf,
                sizes=sizes, domain=domain,
            )
            if transform.ok:
                plan = self.get(transform.program, analysis=None)
            else:
                plan = base
            sp.set(ok=transform.ok)
        entry = (plan, transform)
        self._demand[key] = entry
        while len(self._demand) > self.capacity:
            self._demand.popitem(last=False)
        return entry

    # -- physical plans ----------------------------------------------------

    def warm(
        self,
        plan: CompiledPlan,
        domain: int,
        buckets: tuple[int, ...] = (128, 256),
    ) -> int:
        """Pre-trace the hot kernels for each (IDB arity, capacity bucket).

        Pass the *actual* table capacities (known after materialization —
        see ``MaterializedInstance``) so query selections and the small-side
        merge/sort shapes are hot; shapes that only appear as a table grows
        still trace on first touch.  Returns the number of executables
        traced (0 on a fully warm cache).
        """
        with _TRACE.span(
            "plan_cache.warm", "serve",
            fingerprint=plan.fingerprint, buckets=list(buckets),
        ) as sp:
            traced = self._warm_impl(plan, domain, buckets)
            sp.set(traced=traced)
            return traced

    def _warm_impl(
        self, plan: CompiledPlan, domain: int, buckets: tuple[int, ...]
    ) -> int:
        arities = {plan.strat.pred_arity(p) for p in plan.strat.idb} | {
            plan.program.arity_of(p) for p in plan.strat.edb
        }
        traced = 0
        small = min(buckets)
        for arity in sorted(arities):
            sm = _sort_pad(jnp.zeros((1, arity), jnp.int32), small, domain)
            for bucket in buckets:
                key = (plan.fingerprint, bucket, arity, domain)
                if key in self._warmed:
                    continue
                self._warmed.add(key)
                dummy = jnp.zeros((bucket // 2, arity), jnp.int32)
                srt = _sort_pad(dummy, bucket, domain)
                _dedup_sorted(srt, domain)
                # the steady-state serving merge is (table_cap, small Δ) →
                # table_cap; (b, b) → 2b is the growth merge
                _merge_sorted(srt, pad_delta(sm, bucket), bucket, domain)
                _merge_sorted(srt, srt, 2 * bucket, domain)
                lov = jnp.zeros((arity,), jnp.int32)
                for col in range(arity):   # every single-column bound pattern
                    mask = tuple(i == col for i in range(arity))
                    _select_rows(srt, lov, lov, mask)
                traced += 1
        return traced

    def select(
        self, rows: jax.Array, where: dict[int, int | tuple[int, int]]
    ) -> tuple[jax.Array, int]:
        """Bound-column selection; executables shared across same-shape calls."""
        arity = rows.shape[1]
        lov = np.zeros((arity,), np.int32)
        hiv = np.zeros((arity,), np.int32)
        mask = [False] * arity
        for col, bound in where.items():
            if not 0 <= col < arity:
                raise IndexError(f"column {col} out of range for arity {arity}")
            lo, hi = bound if isinstance(bound, tuple) else (bound, bound)
            lov[col], hiv[col], mask[col] = lo, hi, True
        out, count = _select_rows(
            rows, jnp.asarray(lov), jnp.asarray(hiv), tuple(mask)
        )
        return out, int(count)

    def stats(self) -> dict:
        return {
            "plans": len(self._plans),
            "demand_plans": len(self._demand),
            "hits": self.hits,
            "misses": self.misses,
            "warmed_buckets": len(self._warmed),
        }


_DEFAULT: PlanCache | None = None


def default_cache() -> PlanCache:
    """Process-wide cache: all instances/servers share warm executables."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = PlanCache()
    return _DEFAULT
