"""Faults planted under the timed path, to show that ``correct`` catches them.

Each entry patches a public method of ``MaterializedInstance`` for the
length of a ``with planted(name):`` block.  ``bench/run.py --fault <name>``
and the tests use them; the benchmark's own runs never do.

* ``stale_read`` — the control: queries claim the epoch they pinned but
  answer from the epoch before it (each transaction keeps a pin on its
  base epoch), breaking the snapshot-read guarantee.
* ``txn_unchanged`` — a transaction is acknowledged but leaves the state
  as it was (a step that returns its state unchanged).
* ``txn_half`` — a transaction applies the first half of each operation's
  rows and drops the rest (half of the batch left out).
* ``answer_altered`` — every fifth answer has one row changed where it is
  produced.
"""

from __future__ import annotations

import contextlib

import numpy as np

FAULTS = ("stale_read", "txn_unchanged", "txn_half", "answer_altered")


def _stale_apply(orig):
    def apply_txn(self, ops, deadline_check=None):
        base = self.pin()
        stats = orig(self, ops, deadline_check=deadline_check)
        prev = self.__dict__.setdefault("_bench_prev", {})
        prev[self.epoch] = base             # the epoch before each publish
        for e in sorted(prev)[:-4]:
            prev.pop(e).release()
        return stats
    return apply_txn


def _stale_query(orig):
    def query(self, rel, *, where=None, snapshot=None, **kw):
        if snapshot is not None:
            snapshot = self.__dict__.get("_bench_prev", {}).get(
                snapshot.epoch, snapshot)
        return orig(self, rel, where=where, snapshot=snapshot, **kw)
    return query


def _altered_query(orig):
    count = [0]

    def query(self, rel, **kw):
        rows = np.array(orig(self, rel, **kw))
        count[0] += 1
        if count[0] % 5 == 0:
            if rows.size:
                rows[-1, -1] += 1
            else:
                rows = np.zeros((1, 2), np.int32)
        return rows
    return query


def _unchanged_txn(orig):
    def apply_txn(self, ops, deadline_check=None):
        from repro.serve_datalog.instance import UpdateStats

        ops = list(ops)
        return UpdateStats(relation="+".join(rel for _op, rel, _r in ops),
                           requested=sum(len(r) for _o, _rel, r in ops),
                           kind="txn", epoch=self.epoch)
    return apply_txn


def _half_txn(orig):
    def apply_txn(self, ops, deadline_check=None):
        half = [(op, rel, np.asarray(rows)[: (len(rows) + 1) // 2])
                for op, rel, rows in ops]
        return orig(self, half, deadline_check=deadline_check)
    return apply_txn


_PATCHES = {
    "stale_read": {"query": _stale_query, "apply_txn": _stale_apply},
    "answer_altered": {"query": _altered_query},
    "txn_unchanged": {"apply_txn": _unchanged_txn},
    "txn_half": {"apply_txn": _half_txn},
}


@contextlib.contextmanager
def planted(name: str | None):
    """Patch fault ``name`` in for the block (no fault for ``None``)."""
    if name is None:
        yield
        return
    from repro.serve_datalog import MaterializedInstance

    origs = {attr: getattr(MaterializedInstance, attr) for attr in _PATCHES[name]}
    for attr, wrap in _PATCHES[name].items():
        setattr(MaterializedInstance, attr, wrap(origs[attr]))
    try:
        yield
    finally:
        for attr, orig in origs.items():
            setattr(MaterializedInstance, attr, orig)
