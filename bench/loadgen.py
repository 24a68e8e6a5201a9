"""The one traffic generator: every mix in ``bench/traffic/<mix>.json`` is data for it.

A mix file holds three groups of parameters:

* ``writer`` — one closed-loop client.  Each transaction inserts
  ``insert`` new rows of the configuration's write relation and retracts
  ``retract`` rows it holds now.  New rows are pairs of distinct ids
  already in the active domain, never rows the relation already holds.
  The client sends its next transaction only once the previous one is
  acknowledged and visible, so the sequence of transactions is fixed by
  the seed whatever the server's speed.
* ``readers`` — open-loop point queries ``<query relation>(src=s)``:
  Poisson arrivals at ``rate_per_s``, sources Zipf-distributed with
  exponent ``zipf_s`` over the active domain.  Ranks map to ids through a
  permutation drawn from the seed, so the hot keys are not the low ids.
* ``warmup`` — ``txns``, the most writer transactions set-up applies
  before the window; it stops earlier at the first that builds no
  program.

The deployment's data and the traffic are drawn in the dataset's own ids
from fixed streams (the configuration's ``data_seed``, the mix's
``traffic_seed``).  ``--seed`` draws a permutation of the ids in use and
the whole run, data and traffic, is served under it: every seed does the
same work under other labels, so runs of different seeds compare like
runs of one seed.

Poisson arrivals and Zipf sampling follow ``repro.loadgen.arrivals``;
nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

WRITER_STREAM, READER_STREAM, LABEL_STREAM = 1, 2, 3


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


class Labels:
    """A permutation, drawn from ``seed``, of the ids that ``facts`` use.

    It maps the set of ids in use onto itself, so the active domain and
    every size stay as they are.
    """

    def __init__(self, facts: dict[str, np.ndarray], seed: int):
        self.ids = np.unique(np.concatenate([f.ravel() for f in facts.values()]))
        perm = seed_rng(seed, LABEL_STREAM).permutation(self.ids)
        self.lut = np.zeros(int(self.ids.max()) + 1, np.int32)
        self.lut[self.ids] = perm

    def __call__(self, rows: np.ndarray) -> np.ndarray:
        return self.lut[np.asarray(rows)].astype(np.int32)

    def facts(self, facts: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        return {rel: np.unique(self(rows), axis=0) for rel, rows in facts.items()}


def zipf_sources(ids: np.ndarray, s: float, k: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``k`` draws from ``ids`` with P(rank r) ∝ r^-s, ranks mapped to ids
    through a permutation taken from ``rng``."""
    ranks = np.arange(1, len(ids) + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -s)
    cdf /= cdf[-1]
    by_rank = rng.permutation(ids)
    picks = np.searchsorted(cdf, rng.random(k), side="right")
    return by_rank[np.minimum(picks, len(ids) - 1)].astype(np.int64)


def poisson_times(rate: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets in [0, seconds) of a Poisson process at ``rate``/s."""
    if rate <= 0:
        raise ValueError(f"rate must be > 0, got {rate}")
    out, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= seconds:
            return np.asarray(out)
        out.append(t)


class Writer:
    """The closed-loop writer's transaction stream (see module docstring)."""

    def __init__(self, mix: dict, rows: np.ndarray, rel: str,
                 labels: Labels):
        """``rows``: the relation's rows in the dataset's own ids."""
        w = mix["writer"]
        if w.get("loop") != "closed" or w.get("clients", 1) != 1:
            raise ValueError("the writer is one closed-loop client")
        self.n_insert = int(w["insert"])
        self.n_retract = int(w["retract"])
        self.rel = rel
        self.ids = labels.ids
        self.labels = labels
        self.rng = seed_rng(mix["traffic_seed"], WRITER_STREAM)
        self.held = {tuple(r) for r in rows.tolist()}

    def next_txn(self) -> list[tuple[str, str, np.ndarray]]:
        """The next transaction as ``[(op, rel, rows)]`` under the run's
        labels; updates what the client holds, as if it commits."""
        ops = []
        if self.n_retract:
            held = np.asarray(sorted(self.held), np.int32)
            gone = held[self.rng.choice(len(held), self.n_retract,
                                        replace=False)]
            ops.append(("delete", self.rel, gone))
        free = len(self.ids) * (len(self.ids) - 1) - len(self.held)
        if free < self.n_insert:
            raise ValueError(f"only {free} new rows left over {len(self.ids)} ids")
        new: list[tuple[int, int]] = []
        while len(new) < self.n_insert:
            a, b = (int(v) for v in self.rng.choice(self.ids, 2))
            if a != b and (a, b) not in self.held:
                self.held.add((a, b))
                new.append((a, b))
        if ops:
            for r in ops[0][2].tolist():
                self.held.discard(tuple(r))
        if new:
            ops.insert(0, ("insert", self.rel, np.asarray(new, np.int32)))
        return [(op, rel, self.labels(rows)) for op, rel, rows in ops]


def reader_schedule(mix: dict, labels: Labels,
                    seconds: float) -> tuple[np.ndarray, np.ndarray]:
    """(due offsets in seconds, source ids under the run's labels) of the
    window's point queries."""
    r = mix["readers"]
    if r.get("loop") != "open" or r.get("arrivals") != "poisson":
        raise ValueError("readers are an open-loop Poisson stream")
    rng = seed_rng(mix["traffic_seed"], READER_STREAM)
    times = poisson_times(float(r["rate_per_s"]), seconds, rng)
    sources = zipf_sources(labels.ids, float(r["zipf_s"]), len(times), rng)
    return times, labels(sources).astype(np.int64)
