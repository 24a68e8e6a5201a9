"""Decide ``correct``: what the timed path produced against the plain reference.

Every number here is a count of disagreements, compared exactly (limit 0):

* ``answers_wrong`` — served answers that differ from the reference's
  answer at the epoch the query read (rows of another source, a duplicate,
  a missing or extra row), or that read an epoch no acknowledged
  transaction published;
* ``never_answered`` — queries due in the window and transactions sent in
  it that got no result at all;
* ``epochs_off`` — acknowledged transactions that did not publish exactly
  the next epoch (one epoch per transaction, in order);
* ``fixpoint_off`` — rows of the final published epoch, every relation,
  that are missing, extra or duplicated against the reference over the
  facts every acknowledged transaction left behind;
* ``wal_off`` — acknowledged transactions whose committed bracket is
  missing from the durable log or differs from what was sent, and committed
  brackets that no acknowledged transaction accounts for;
* ``path_off`` — transactions (and the set-up) whose relation did not run
  on the update path the configuration requires.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"answers_wrong": 0, "never_answered": 0, "epochs_off": 0,
          "fixpoint_off": 0, "wal_off": 0, "path_off": 0}


def _apply(state: dict[str, set], ops) -> None:
    for op, rel, rows in ops:
        tuples = {tuple(r) for r in np.asarray(rows).tolist()}
        if op == "insert":
            state[rel] |= tuples
        else:
            state[rel] -= tuples


def _rows(tuples: set) -> np.ndarray:
    if not tuples:
        return np.zeros((0, 2), np.int32)
    return np.asarray(sorted(tuples), np.int32)


def _set_off(got: np.ndarray, want: set) -> int:
    """Missing + extra + duplicated rows of ``got`` against ``want``."""
    got_set = {tuple(r) for r in np.asarray(got).tolist()}
    return len(got_set ^ want) + (len(got) - len(got_set))


def _dense_off(got: np.ndarray, want: np.ndarray) -> int:
    """Missing + extra + duplicated + out-of-range rows of ``got`` against
    the dense boolean ``want``."""
    n = want.shape[0]
    got = np.asarray(got).reshape(-1, 2)
    ok = (got >= 0).all(axis=1) & (got < n).all(axis=1)
    m = np.zeros((n, n), bool)
    m[got[ok, 0], got[ok, 1]] = True
    return int((m != want).sum()) + int(ok.sum() - m.sum()) + int((~ok).sum())


def _answer_ok(src: int, rows, want_row: np.ndarray) -> bool:
    rows = np.asarray(rows).reshape(-1, 2)
    if rows.size and not (rows[:, 0] == src).all():
        return False
    col = np.sort(rows[:, 1])
    want = np.flatnonzero(want_row)
    return col.shape == want.shape and np.array_equal(col, want)


def evaluate(dep, facts0: dict, n: int, epoch0: int, acked: list,
             answers: list, final: dict, wal_txns: list, wal_floor: int,
             never: int, path_off: int) -> dict[str, int]:
    """Every number of :data:`LIMITS`.

    ``acked``: ``[(ops, epoch)]`` of the acknowledged transactions in the
    order sent, warm-up included; ``answers``: ``[(src, epoch, rows)]`` of
    the served queries; ``final``: ``{"epoch": e, rel: rows}`` read from
    the last published epoch; ``wal_txns``: ``[(epoch, ops)]`` committed in
    the durable log above its snapshot epoch ``wal_floor``.
    """
    out = dict.fromkeys(LIMITS, 0)
    out["never_answered"] = never
    out["path_off"] = path_off

    # the EDB after each acknowledged transaction, keyed by its epoch
    state = {rel: {tuple(r) for r in facts0[rel].tolist()} for rel in dep.EDB}
    by_epoch: dict[int, list] = {}
    for src, epoch, rows in answers:
        by_epoch.setdefault(epoch, []).append((src, rows))
    expected, last = epoch0, epoch0

    def judge(epoch: int) -> None:
        group = by_epoch.pop(epoch, [])
        if not group and epoch != final["epoch"]:
            return
        # one copy to the host per epoch: indexing on the device would build
        # a program per number of sources
        ref = np.asarray(dep.reference({r: _rows(state[r]) for r in dep.EDB}, n))
        out["answers_wrong"] += sum(not _answer_ok(s, rows, ref[s])
                                    for s, rows in group)
        if epoch == final["epoch"]:
            out["fixpoint_off"] += _dense_off(final[dep.IDB], ref)
            for rel in dep.EDB:
                out["fixpoint_off"] += _set_off(final[rel], state[rel])

    judge(epoch0)
    for ops, epoch in acked:
        expected += 1
        if epoch != expected:
            out["epochs_off"] += 1
            expected = epoch
        _apply(state, ops)
        last = epoch
        judge(epoch)
    # answers that read an epoch no acknowledged transaction published
    out["answers_wrong"] += sum(len(g) for g in by_epoch.values())
    if final["epoch"] != last:
        out["fixpoint_off"] += 1

    def canon(ops) -> list:
        return sorted((op, rel, np.unique(np.asarray(rows, np.int32).reshape(
            -1, 2), axis=0).tobytes()) for op, rel, rows in ops)

    logged = {}
    for epoch, ops in wal_txns:
        if epoch in logged:
            out["wal_off"] += 1
        logged[epoch] = canon(ops)
    for ops, epoch in acked:
        if epoch <= wal_floor:
            continue
        got = logged.pop(epoch, None)
        out["wal_off"] += got is None or got != canon(ops)
    out["wal_off"] += len(logged)
    return out
