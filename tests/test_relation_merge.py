"""The tuple table's merge: exact against a numpy reference, on both paths.

``_merge_sorted`` merges on the compact keys alone (one key sort, rows
unpacked from the keys) when ``domain ** arity`` fits in 31 bits, and sorts
whole rows lexicographically otherwise.  Either way the result is the sorted
union of the two tables' valid rows, padded with SENTINEL to ``capacity``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import Engine, wide_preds
from repro.core.parser import parse
from repro.core.relation import TupleRelation, _merge_sorted
from repro.obs.trace import TRACER
from repro.relational.sort import SENTINEL, compact_key_fits

S = int(SENTINEL)


def _table(rng, n_valid, shape0, arity, hi):
    """Sorted unique rows in ``[0, hi)``, SENTINEL-padded to ``shape0``."""
    rows = np.unique(rng.integers(0, hi, size=(n_valid, arity)), axis=0)
    while len(rows) < n_valid:   # top up after collisions
        more = rng.integers(0, hi, size=(n_valid, arity))
        rows = np.unique(np.concatenate([rows, more]), axis=0)
    rows = rows[rng.permutation(len(rows))[:n_valid]]
    rows = rows[np.lexsort(rows.T[::-1])] if n_valid else rows.reshape(0, arity)
    out = np.full((shape0, arity), S, np.int32)
    out[:n_valid] = rows
    return out


def _table_from(rows, shape0):
    out = np.full((shape0, rows.shape[1]), S, np.int32)
    out[: len(rows)] = rows
    return out


def _reference(a, b, capacity):
    rows = np.concatenate([a, b])
    rows = rows[(rows != S).all(axis=1)]
    rows = rows[np.lexsort(rows.T[::-1])]
    out = np.full((capacity, a.shape[1]), S, np.int32)
    out[: len(rows)] = rows
    return out


# (arity, domain, (a rows, a shape), (b rows, b shape), capacity)
CASES = {
    "arity1_key": (1, 1000, (40, 64), (9, 16), 64),
    "arity2_key": (2, 50, (40, 64), (9, 16), 64),
    "arity3_key": (3, 30, (40, 64), (9, 16), 64),
    "lexsort_wide_domain": (2, 1 << 16, (40, 64), (9, 16), 64),
    "lexsort_arity3": (3, 2000, (40, 64), (9, 16), 64),
    "lexsort_unbounded": (2, 0, (40, 64), (9, 16), 64),
    "capacity_below_total": (2, 50, (5, 16), (3, 8), 8),
    "capacity_equal_total": (2, 50, (16, 16), (8, 8), 24),
    "capacity_above_total": (2, 50, (10, 16), (6, 8), 64),
    "empty_delta": (2, 50, (40, 64), (0, 16), 64),
    "all_pad_table": (2, 50, (0, 64), (9, 16), 64),
    "delta_larger_than_table": (2, 50, (7, 8), (100, 128), 128),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_merge_sorted_matches_numpy_reference(case):
    arity, domain, (na, sa), (nb, sb), capacity = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    hi = domain if domain else 1 << 20
    both = _table(rng, na + nb, na + nb, arity, hi)   # disjoint a and b
    pick = rng.permutation(na + nb)
    a = _table_from(both[np.sort(pick[:na])], sa)
    b = _table_from(both[np.sort(pick[na:])], sb)
    got = np.asarray(_merge_sorted(jnp.asarray(a), jnp.asarray(b), capacity, domain))
    np.testing.assert_array_equal(got, _reference(a, b, capacity))


@pytest.mark.parametrize("domain", [50, 1 << 16])
def test_self_merge_keeps_duplicates(domain):
    """``PlanCache.warm`` merges a table with itself into twice the bucket."""
    rng = np.random.default_rng(3)
    a = _table(rng, 12, 16, 2, 50)
    got = np.asarray(_merge_sorted(jnp.asarray(a), jnp.asarray(a), 32, domain))
    np.testing.assert_array_equal(got, _reference(a, a, 32))


@pytest.mark.parametrize("arity, domain", [(1, 1000), (2, 5000), (3, 1000)])
def test_key_merge_indexes_no_row(arity, domain):
    """On compact keys the merge is one sort: no gather, scatter or loop
    walks the big table row by row."""
    assert compact_key_fits(arity, domain)
    a = jnp.zeros((1 << 12, arity), jnp.int32)
    b = jnp.zeros((1 << 7, arity), jnp.int32)
    hlo = _merge_sorted.lower(a, b, 1 << 12, domain).as_text()
    assert "stablehlo.sort" in hlo
    for op in ("gather", "scatter", "while"):
        assert op not in hlo, op


def test_small_deltas_share_one_merge_program():
    """Deltas under 1/64 of the table are padded to one shape, so they merge
    through one program; the result stays the exact sorted union."""
    rng = np.random.default_rng(4)
    table = _table(rng, 10_000 + 300, 10_000 + 300, 2, 200)
    rel = TupleRelation.from_numpy("r", table[:10_000], 200)
    assert rel.capacity == 1 << 14
    before = _merge_sorted._cache_size()
    for lo, hi, bucket in ((10_000, 10_003, 128), (10_003, 10_200, 256)):
        delta = _table_from(table[lo:hi], bucket)
        merged = rel.merge(jnp.asarray(delta), hi - lo)
        want = _reference(np.asarray(rel.rows), delta, rel.capacity)
        np.testing.assert_array_equal(np.asarray(merged.rows), want)
    assert _merge_sorted._cache_size() == before + 1


@pytest.fixture
def tracing():
    TRACER.enable()
    try:
        yield TRACER
    finally:
        TRACER.disable()
        TRACER.clear()


def _merge_events(tracer):
    return [s for s in tracer.spans() if s.name == "tuple.merge"]


@pytest.mark.parametrize(
    "domain, path", [(10, "key_sort"), (1 << 16, "lexsort"), (0, "lexsort")]
)
def test_merge_records_its_path_once(tracing, domain, path):
    rel = TupleRelation.from_numpy("r", np.array([[5, 1], [1, 1]], np.int32), domain)
    delta = _table_from(np.array([[3, 3], [9, 9], [9, 8]], np.int32), 4)
    tracing.clear()
    merged = rel.merge(jnp.asarray(delta), 3)
    (ev,) = _merge_events(tracing)
    assert ev.args == {
        "path": path, "table_rows": 2, "delta_rows": 3, "capacity": 128,
    }
    assert merged.count == 5


def test_merge_records_nothing_when_tracing_is_off():
    TRACER.disable()
    TRACER.clear()
    rel = TupleRelation.from_numpy("r", np.array([[5, 1]], np.int32), 10)
    rel.merge(jnp.asarray(_table_from(np.array([[3, 3]], np.int32), 4)), 1)
    assert _merge_events(TRACER) == []


def test_wide_preds_follow_computed_values():
    prog = parse(
        """
        tc(x, y) :- arc(x, y).
        tc(x, y) :- tc(x, z), arc(z, y).
        best(x, MIN(y)) :- tc(x, y).
        total(x, SUM(y)) :- arc(x, y).
        far(x, MIN(y+1)) :- arc(x, y).
        tagged(x, 99) :- arc(x, _).
        reads(x, s) :- total(x, s).
        """
    )
    assert wide_preds(prog, 50) == {"total", "far", "tagged", "reads"}
    assert wide_preds(prog, 100) == {"total", "far", "reads"}


def test_engine_keeps_values_beyond_the_domain():
    """Sums beyond the domain alias on compact keys ((0, 15) and (1, 5) both
    pack to 15 over domain 10); such a table keeps whole rows."""
    arc = np.array([[0, 7], [0, 8], [1, 5], [2, 9], [2, 3], [3, 1]], np.int32)
    out = Engine().run(
        """
        total(x, SUM(y)) :- arc(x, y).
        copy(x, s) :- total(x, s).
        """,
        {"arc": arc},
    )
    want = {(0, 15), (1, 5), (2, 12), (3, 1)}
    assert set(map(tuple, out["total"].tolist())) == want
    assert set(map(tuple, out["copy"].tolist())) == want
