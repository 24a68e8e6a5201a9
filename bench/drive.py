"""Drive a ``DatalogServer`` with one closed-loop writer and open-loop readers.

The readers' queries are submitted on a sender thread of their own, each
at the time it is due, whatever the server is doing: the schedule is open
loop.  The calling thread serves the server's loop (``step()``) as a user
of it would, and runs the writer: it submits a transaction, polls without
blocking until the transaction is acknowledged and its epoch's device
arrays are computed, stamps it visible, and submits the next.  Neither
thread waits on the device for the other.

Host spans (``jax.profiler.TraceAnnotation``, names ``bench.*``) mark what
the calling thread is doing, so a profiler trace can attribute device idle
time to it.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.profiler import TraceAnnotation

POLL_S = 0.0005


def published_buffers(inst) -> list:
    """The device arrays of the latest published epoch."""
    from repro.core.versioned_store import handle_buffers

    with inst.pin() as snap:
        bufs = [b for h in snap.handles.values() for b in handle_buffers(h)]
        bufs += jax.tree_util.tree_leaves(snap.meta)
    return [b for b in bufs if isinstance(b, jax.Array)]


def published_ready(inst) -> None:
    """Wait until the latest published epoch's device arrays are computed."""
    jax.block_until_ready(published_buffers(inst))


@dataclass
class Txn:
    ops: list
    submitted: float
    base_epoch: int
    rid: int = -1
    visible: float | None = None        # acknowledged and readable
    result: object = None               # UpdateStats or RequestError
    bufs: list | None = None            # its epoch's arrays, once acknowledged


@dataclass
class Query:
    src: int
    due: float
    sent: float | None = None
    rid: int = -1
    answered: float | None = None
    result: object = None               # rows or RequestError
    epoch: int = -1


@dataclass
class Client:
    srv: object
    writer: object                      # loadgen.Writer
    query_rel: str
    txns: list = field(default_factory=list)
    queries: list = field(default_factory=list)
    _inflight: Txn | None = None
    _pending: dict = field(default_factory=dict)     # rid -> Query
    # the server's submissions are not thread-safe: the sender thread and
    # the writer's submissions take turns under this lock
    _lock: threading.Lock = field(default_factory=threading.Lock)
    on_txn_done: object = None          # called after each transaction is reaped

    def _step(self) -> bool:
        more = self.srv.step()
        self._collect()
        return more

    # -- transactions -------------------------------------------------------

    def _submit_txn(self) -> Txn:
        ops = self.writer.next_txn()
        with self._lock:
            tx = self.srv.transaction()
            for op, rel, rows in ops:
                tx = tx.insert(rel, rows) if op == "insert" else tx.retract(rel, rows)
            t = Txn(ops, time.perf_counter(), self.srv.instance.epoch)
            t.rid = tx.submit()
        self._step()                    # admits it and starts the writer
        self.txns.append(t)
        self._inflight = t
        return t

    def _acknowledged(self) -> bool:
        """True once the in-flight transaction is acknowledged; takes its
        epoch's arrays then, without waiting for them."""
        t = self._inflight
        if t.bufs is not None:
            return True
        if self.srv.instance.epoch <= t.base_epoch and t.rid not in self.srv.done:
            return False
        while t.rid not in self.srv.done and self._step():
            pass
        t.result = self.srv.done.pop(t.rid, None)
        t.bufs = published_buffers(self.srv.instance)
        return True

    def _visible(self) -> bool:
        """Acknowledged and its epoch's arrays computed (never blocks)."""
        return self._acknowledged() and all(b.is_ready() for b in self._inflight.bufs)

    def _finish_txn(self) -> Txn:
        """Stamp the in-flight transaction visible and let it go."""
        t = self._inflight
        t.visible = time.perf_counter()
        t.bufs = None
        self._inflight = None
        if self.on_txn_done is not None:
            self.on_txn_done()
        return t

    # -- queries ------------------------------------------------------------

    def _collect(self) -> None:
        done = self.srv.done
        now = time.perf_counter()
        with self._lock:
            for rid in [r for r in self._pending if r in done]:
                q = self._pending.pop(rid)
                q.answered = now
                q.result = done.pop(rid)

    def _send(self, q: Query) -> None:
        with self._lock:
            q.sent = time.perf_counter()
            q.rid = self.srv.submit_query(self.query_rel, src=int(q.src))
            self._pending[q.rid] = q

    def _sender(self, stop: threading.Event) -> None:
        """Submit every query of the window at the time it is due."""
        for q in self.queries:
            delay = q.due - time.perf_counter()
            if delay > 0 and stop.wait(delay):
                return
            self._send(q)

    # -- phases ---------------------------------------------------------------

    def warm_queries(self, sources) -> None:
        """One query per source, answered before the window opens."""
        for s in sources:
            self._send(Query(int(s), time.perf_counter()))
            while self._step():
                pass

    def window(self, due_offsets, sources, seconds: float) -> tuple[float, float]:
        """Serve the window; returns its (start, end) on ``perf_counter``."""
        t0 = time.perf_counter()
        t_end = t0 + seconds
        due = t0 + np.asarray(due_offsets, np.float64)
        self.queries = [Query(int(s), float(d)) for s, d in zip(sources, due)
                        if d < t_end]
        stop = threading.Event()
        sender = threading.Thread(target=self._sender, args=(stop,),
                                  name="bench-readers", daemon=True)
        with TraceAnnotation("bench.window"):
            sender.start()
            with TraceAnnotation("bench.submit_txn"):
                self._submit_txn()
            while time.perf_counter() < t_end:
                # the writer first, so a steady stream of queries cannot
                # hold back its next transaction
                if self._visible():
                    self._finish_txn()
                    if time.perf_counter() < t_end:
                        with TraceAnnotation("bench.submit_txn"):
                            self._submit_txn()
                elif self.srv.queue:
                    with TraceAnnotation("bench.serve_queries"):
                        self._step()
                else:
                    with TraceAnnotation("bench.wait"):
                        time.sleep(POLL_S)
        t_closed = time.perf_counter()
        # every query due inside the window is sent and answered, late or not
        with TraceAnnotation("bench.drain"):
            sender.join()
            while self._step():         # serves the queue, then reaps the writer
                pass
            if self._inflight is not None:
                self._acknowledged()
                jax.block_until_ready(self._inflight.bufs)
                self._finish_txn()
        return t0, t_closed

    def query_epochs(self) -> None:
        """The epoch each answered query read, from the server's records."""
        by_rid = {q.rid: q for q in self.queries}
        for rec in self.srv.stats.snapshot():
            q = by_rid.get(rec.rid)
            if q is not None and rec.kind == "query":
                q.epoch = rec.epoch


def replay(inst, writer, max_txns: int, log=None) -> list:
    """Apply the writer's next transactions to ``inst`` directly, each
    waited for on the device, until one builds no program or ``max_txns``
    have run.  Returns ``[(ops, epoch published)]``."""
    from bench.clock import BuildClock

    done = []
    while len(done) < max_txns:
        t = time.perf_counter()
        clock = BuildClock().start()
        ops = writer.next_txn()
        done.append((ops, inst.apply_txn(ops).epoch))
        published_ready(inst)
        clock.stop()
        if log is not None:
            log(f"warm-up transaction {len(done)}: {time.perf_counter() - t:.3f} s, "
                f"{clock.compiles} programs ({clock.cache_hits} from the cache)")
        if clock.compiles == 0:
            break
    return done
