"""Traffic repeats from its seed: arrivals, Zipf sources, transactions, labels."""

from __future__ import annotations

import numpy as np
import pytest

from bench import loadgen

MIX = {"traffic_seed": 0,
       "writer": {"loop": "closed", "clients": 1, "insert": 3, "retract": 2},
       "readers": {"loop": "open", "arrivals": "poisson", "rate_per_s": 50.0,
                   "zipf_s": 0.99},
       "warmup": {"txns": 2}}
FACTS = {"arc": np.array([[0, 1], [1, 2], [2, 3], [3, 7], [7, 0], [5, 6]], np.int32),
         "other": np.array([[6, 5]], np.int32)}
BIG = 2**31 + 12345


def _traffic(seed, traffic_seed=0):
    labels = loadgen.Labels(FACTS, seed)
    mix = dict(MIX, traffic_seed=traffic_seed)
    w = loadgen.Writer(mix, FACTS["arc"], "arc", labels)
    txns = [w.next_txn() for _ in range(5)]
    due, src = loadgen.reader_schedule(mix, labels, 4.0)
    return labels.facts(FACTS), txns, due, src


def _same(a, b):
    fa, ta, da, sa = a
    fb, tb, db, sb = b
    return (all(np.array_equal(fa[k], fb[k]) for k in fa)
            and all(x[0] == y[0] and x[1] == y[1] and np.array_equal(x[2], y[2])
                    for p, q in zip(ta, tb) for x, y in zip(p, q))
            and np.array_equal(da, db) and np.array_equal(sa, sb))


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_same_seed_same_traffic(seed):
    assert _same(_traffic(seed), _traffic(seed))


def test_other_seed_other_labels_same_work():
    a, b = _traffic(1), _traffic(2)
    assert not _same(a, b)
    assert np.array_equal(a[2], b[2])               # the same arrivals
    for p, q in zip(a[1], b[1]):                    # the same sizes
        assert [(op, len(r)) for op, _rel, r in p] == [(op, len(r)) for op, _rel, r in q]
    assert not _same(_traffic(1, 0), _traffic(1, 1))


def test_relabel_keeps_sizes_and_ids():
    for seed in (1, BIG):
        facts = loadgen.Labels(FACTS, seed).facts(FACTS)
        ids0 = np.unique(np.concatenate([f.ravel() for f in FACTS.values()]))
        ids1 = np.unique(np.concatenate([f.ravel() for f in facts.values()]))
        assert np.array_equal(ids0, ids1)
        assert {k: len(v) for k, v in facts.items()} == {k: len(v) for k, v in FACTS.items()}


def test_transactions_insert_new_rows_and_retract_held_ones():
    facts, txns, _due, _src = _traffic(3)
    held = {tuple(r) for r in facts["arc"].tolist()}
    assert len(held) == len(FACTS["arc"])
    ids = set(np.unique(np.concatenate([f.ravel() for f in facts.values()])).tolist())
    for ops in txns:
        kinds = {op: rows for op, _rel, rows in ops}
        ins = {tuple(r) for r in kinds["insert"].tolist()}
        gone = {tuple(r) for r in kinds["delete"].tolist()}
        assert len(ins) == 3 and len(gone) == 2
        assert not ins & held and gone <= held
        assert all(a != b and a in ids and b in ids for a, b in ins)
        held = (held - gone) | ins


def test_arrivals_and_zipf_skew():
    rng = loadgen.seed_rng(5, 2)
    times = loadgen.poisson_times(20.0, 100.0, rng)
    assert np.all(np.diff(times) > 0) and 0 <= times[0] and times[-1] < 100.0
    assert 1800 < len(times) < 2200
    ids = np.arange(1000)
    src = loadgen.zipf_sources(ids, 0.99, 20000, loadgen.seed_rng(5, 3))
    counts = np.bincount(src, minlength=1000)
    top = np.sort(counts)[::-1]
    assert top[0] > 10 * np.median(counts)     # skewed
    assert set(np.argsort(counts)[-10:]) != set(range(10))   # hot keys not the low ids


def test_writer_refuses_when_no_new_rows_are_left():
    rows = np.array([[0, 1]], np.int32)
    w = loadgen.Writer(MIX, rows, "arc", loadgen.Labels({"arc": rows}, 0))
    with pytest.raises(ValueError):
        w.next_txn()
