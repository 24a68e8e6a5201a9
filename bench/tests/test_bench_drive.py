"""The window keeps its readers open loop while the server's ``step()`` blocks.

A stand-in server answers each query batch only after a long wait, as a
query queued behind a device merge does.  Queries must still be sent when
due, and the writer's transaction must be stamped visible once its epoch's
arrays are ready, not once a query wait ends.
"""

from __future__ import annotations

import time
from collections import deque
from types import SimpleNamespace

import numpy as np

from bench import drive

BLOCK_S = 0.15


class _Tx:
    def __init__(self, srv):
        self.srv, self.ops = srv, []

    def insert(self, rel, rows):
        self.ops.append(("insert", rel, rows))
        return self

    def submit(self):
        return self.srv._enqueue("txn")


class _BlockingServer:
    """Queries wait ``BLOCK_S`` in ``step()``; a transaction publishes the
    next epoch on the first step that admits it."""

    def __init__(self):
        self.queue, self.done, self._next = deque(), {}, 0
        self.instance = SimpleNamespace(epoch=0)

    def _enqueue(self, kind):
        rid, self._next = self._next, self._next + 1
        self.queue.append((rid, kind))
        return rid

    def submit_query(self, rel, src):
        return self._enqueue("query")

    def transaction(self):
        return _Tx(self)

    def step(self):
        if not self.queue:
            return False
        rid, kind = self.queue.popleft()
        if kind == "txn":
            self.instance.epoch += 1
            self.done[rid] = SimpleNamespace(epoch=self.instance.epoch, modes={})
        else:
            batch = [rid]
            while self.queue and self.queue[0][1] == "query":
                batch.append(self.queue.popleft()[0])
            time.sleep(BLOCK_S)
            for r in batch:
                self.done[r] = np.zeros((0, 2), np.int32)
        return bool(self.queue)


class _Writer:
    def next_txn(self):
        return [("insert", "arc", np.array([[0, 1]], np.int32))]


def test_readers_keep_their_schedule_while_step_blocks(monkeypatch):
    monkeypatch.setattr(drive, "published_buffers", lambda inst: [])
    client = drive.Client(_BlockingServer(), _Writer(), "tc")
    due = np.arange(0.0, 1.2, 0.02)
    t0, t1 = client.window(due, np.zeros(len(due), np.int64), 1.2)
    lags = [q.sent - q.due for q in client.queries]
    assert len(lags) == len(due)
    assert max(lags) < BLOCK_S / 3, max(lags)
    assert all(q.answered is not None and q.answered >= q.sent for q in client.queries)
    assert any(q.answered - q.due >= BLOCK_S / 2 for q in client.queries)
    assert len(client.txns) >= 2
    assert all(t.visible is not None and t.visible >= t.submitted for t in client.txns)
