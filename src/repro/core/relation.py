"""Device-resident relations (EOST: state never leaves the device).

Three physical representations, chosen by the engine per-IDB (the paper's
"specialized data structures" lever):

* :class:`TupleRelation`    — sorted ``int32[capacity, arity]`` + count; the
  general representation (program analysis, arbitrary arity).
* :class:`DenseSetRelation` — ``bool[n]`` for unary recursive IDBs (REACH):
  the bit-vector cousin of PBME.
* :class:`DenseAggRelation` — ``int32[n]`` best-value table for recursive
  MIN/MAX aggregates (CC, SSSP): a group-by whose key is the active domain
  *is* a dense array.

Capacities are power-of-two buckets; growth doubles the bucket, which bounds
recompilation (OOF plan-selection happens at bucket granularity).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import TRACER as _TRACE
from repro.relational.sort import (
    SENTINEL,
    compact_key,
    compact_key_fits,
    decode_key,
    lexsort_rows,
    unique_mask,
)

INT_INF = int(SENTINEL)


def next_bucket(n: int, minimum: int = 128) -> int:
    return max(minimum, 1 << int(np.ceil(np.log2(max(n, 1)))))


def empty_delta(arity: int, minimum: int = 128) -> jax.Array:
    """The normalized empty Δ/∇ view: a minimum-bucket SENTINEL table.

    Every non-empty delta produced by ``insert``/``delete`` is a sorted,
    SENTINEL-padded table at a power-of-two capacity bucket; the empty delta
    uses the same shape family (the minimum bucket — ``minimum`` defaults to
    ``next_bucket``'s floor, which every relation-level bucket here shares)
    so downstream code can slice/merge it without special-casing
    ``count == 0``.
    """
    return jnp.full((next_bucket(0, minimum), arity), SENTINEL, jnp.int32)


def pad_delta(rows: jax.Array, capacity: int) -> jax.Array:
    """A merge's delta, SENTINEL-padded to at least ``capacity >> 6`` rows.

    Every delta under 1/64 of a table then merges into it through one
    program, for at most 1/64 more keys to sort: the TPU's compiler takes
    seconds to build a program around a large sort (18 s at 2^25 rows on
    a v5e), and a new delta bucket would otherwise build one mid-stream.
    """
    short = (capacity >> 6) - rows.shape[0]
    if short <= 0:
        return rows
    pad = jnp.full((short, rows.shape[1]), SENTINEL, jnp.int32)
    return jnp.concatenate([rows, pad])


#: The device scope of the tuple table's sort, dedup and merge programs
#: (``jax.named_scope``: op metadata only, so a profiler trace can attribute
#: their device time; program and op names are unchanged).
MERGE_SCOPE = "tuple.merge"


@functools.partial(jax.jit, static_argnames=("capacity", "domain"))
def _sort_pad(rows: jax.Array, capacity: int, domain: int) -> jax.Array:
    with jax.named_scope(MERGE_SCOPE):
        pad = jnp.full((capacity - rows.shape[0], rows.shape[1]), SENTINEL, jnp.int32)
        rows = jnp.concatenate([rows.astype(jnp.int32), pad], axis=0)
        key = compact_key(rows, domain)
        order = jnp.argsort(key) if key is not None else lexsort_rows(rows)
        return rows[order]


@functools.partial(jax.jit, static_argnames=("domain",))
def _dedup_sorted(rows: jax.Array, domain: int) -> tuple[jax.Array, jax.Array]:
    """Sorted rows → (unique rows first + SENTINEL pads, unique count)."""
    with jax.named_scope(MERGE_SCOPE):
        mask = unique_mask(rows)
        kept = jnp.where(mask[:, None], rows, SENTINEL)
        order = jnp.argsort(~mask, stable=True)
        return kept[order], mask.sum()


@functools.partial(jax.jit, static_argnames=("domain",))
def _delete_sorted(
    table: jax.Array, cand: jax.Array, domain: int
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Remove candidate rows from a sorted table.

    ``cand`` is sorted + SENTINEL-padded.  Returns
    ``(removed, removed_count, kept, kept_count)`` — ``removed`` is the
    compacted intersection (the ∇R view, sorted), ``kept`` the table with
    those rows punched out and re-compacted at the original capacity.
    """
    from repro.core.joins import membership

    present = membership(cand, table, domain)
    removed = jnp.where(present[:, None], cand, SENTINEL)
    removed = removed[jnp.argsort(~present, stable=True)]   # compact, sorted
    gone = membership(table, removed, domain)
    keep = ~gone & (table[:, 0] != SENTINEL)
    kept = jnp.where(keep[:, None], table, SENTINEL)
    kept = kept[jnp.argsort(~keep, stable=True)]
    return removed, present.sum(), kept, keep.sum()


@functools.partial(jax.jit, static_argnames=("col",))
def _sorted_by_col(rows: jax.Array, col: int) -> tuple[jax.Array, jax.Array]:
    key = rows[:, col]
    # pads already have SENTINEL keys; stable sort keeps lex order within key
    order = jnp.argsort(key, stable=True)
    srt = rows[order]
    return srt, srt[:, col]


@dataclass
class TupleRelation:
    """Sorted fixed-capacity tuple table."""

    name: str
    arity: int
    rows: jax.Array          # int32[capacity, arity], lex-sorted, pads last
    count: int               # host-side valid-row count (the OOF statistic)
    domain: int              # active-domain size (compact-key eligibility);
                             # 0: values unbounded, rows sort without a key
    _by_col: dict[int, tuple[jax.Array, jax.Array]] = field(default_factory=dict)

    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    @classmethod
    def empty(cls, name: str, arity: int, domain: int, capacity: int = 128):
        rows = jnp.full((capacity, arity), SENTINEL, jnp.int32)
        return cls(name, arity, rows, 0, domain)

    @classmethod
    def from_numpy(cls, name: str, data: np.ndarray, domain: int):
        data = np.asarray(data, dtype=np.int32)
        if data.ndim == 1:
            data = data[:, None]
        data = np.unique(data, axis=0) if data.size else data
        cap = next_bucket(len(data))
        rows = _sort_pad(jnp.asarray(data), cap, domain)
        return cls(name, data.shape[1], rows, int(len(data)), domain)

    def sorted_by(self, col: int) -> tuple[jax.Array, jax.Array]:
        """Relation sorted by one column (join index); cached per column."""
        if col == 0:
            return self.rows, self.rows[:, 0]
        if col not in self._by_col:
            self._by_col[col] = _sorted_by_col(self.rows, col)
        return self._by_col[col]

    def merge(self, delta_rows: jax.Array, delta_count: int) -> "TupleRelation":
        """R ⊎ ΔR keeping the table sorted (ΔR pre-deduped, disjoint from R)."""
        if delta_count == 0:
            return self
        new_count = self.count + delta_count
        cap = self.capacity
        while cap < new_count:
            cap *= 2
        if _TRACE.enabled:
            fits = compact_key_fits(self.arity, self.domain)
            _TRACE.instant(
                MERGE_SCOPE, "tuple", path="key_sort" if fits else "lexsort",
                table_rows=self.count, delta_rows=delta_count, capacity=cap,
            )
        merged = _merge_sorted(
            self.rows, pad_delta(delta_rows, self.capacity), cap, self.domain
        )
        return TupleRelation(self.name, self.arity, merged, new_count, self.domain)

    def insert(self, data: np.ndarray) -> tuple["TupleRelation", jax.Array, int]:
        """Delta-append: dedup incoming rows against the table, merge the rest.

        Returns ``(updated_relation, delta_rows, delta_count)`` where
        ``delta_rows`` holds only the genuinely-new tuples (sorted, SENTINEL
        padded) — the ΔR seed for incremental view maintenance.  The existing
        sorted table is reused by the merge; no full rebuild.
        """
        from repro.core.setdiff import DSDState, set_difference

        data = np.asarray(data, np.int32).reshape(-1, self.arity)
        if data.size == 0:
            return self, empty_delta(self.arity), 0
        data = np.unique(data, axis=0)
        cap = next_bucket(len(data))
        cand = _sort_pad(jnp.asarray(data), cap, self.domain)
        delta_rows, delta_count, _ = set_difference(
            cand, len(data), self.rows, self.count, self.domain,
            DSDState(), mode="opsd",
        )
        return self.merge(delta_rows, delta_count), delta_rows, delta_count

    def delete(self, data: np.ndarray) -> tuple["TupleRelation", jax.Array, int]:
        """Remove a batch of rows (rows not present are ignored).

        Returns ``(updated_relation, removed_rows, removed_count)`` where
        ``removed_rows`` holds exactly the tuples that were present and are
        now gone (sorted, SENTINEL padded) — the ∇R seed for DRed.  The
        handle is immutable: the original relation is untouched, capacity is
        preserved (no shrink — buckets bound recompilation, not memory).
        """
        data = np.asarray(data, np.int32).reshape(-1, self.arity)
        # constants outside [0, domain) cannot be present (the table invariant
        # behind compact keys) — drop them, or the base-``domain`` key packing
        # would alias e.g. (a, domain) onto (a+1, 0) and delete a tuple the
        # caller never named (a table with ``domain`` 0 keeps no key)
        if data.size and self.domain > 0:
            data = data[((data >= 0) & (data < self.domain)).all(axis=1)]
        if data.size == 0 or self.count == 0:
            return self, empty_delta(self.arity), 0
        data = np.unique(data, axis=0)
        cap = next_bucket(len(data))
        return self.delete_rows(_sort_pad(jnp.asarray(data), cap, self.domain))

    def delete_rows(self, cand: jax.Array) -> tuple["TupleRelation", jax.Array, int]:
        """Device-side delete: ``cand`` already sorted + SENTINEL padded."""
        removed, r_count, kept, k_count = _delete_sorted(
            self.rows, cand, self.domain
        )
        r_count = int(r_count)
        if r_count == 0:
            return self, empty_delta(self.arity), 0
        new = TupleRelation(self.name, self.arity, kept, int(k_count), self.domain)
        return new, removed, r_count

    def device_buffers(self) -> tuple[jax.Array, ...]:
        """Every device array this handle owns (reclamation accounting).

        Includes the per-column sort copies cached by :meth:`sorted_by`.
        Handles are immutable, so the buffer set only grows lazily via that
        cache; the ``VersionedStore`` counts these when a superseded epoch
        drops its last reference.
        """
        return (self.rows, *(a for pair in self._by_col.values() for a in pair))

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.rows[: self.count])

    def to_blocks(self) -> tuple[dict, dict[str, np.ndarray]]:
        """(meta, arrays) for the snapshot codec (see ``repro.persist``).

        ``arrays`` holds the full sorted/padded table (memmap-friendly; the
        power-of-two capacity is part of the state — buckets bound
        recompilation, so a restore at the original capacity replays against
        warm executables).  Per-column sort caches are derived state and are
        not serialized.
        """
        meta = {
            "kind": "tuple",
            "arity": self.arity,
            "count": self.count,
            "domain": self.domain,
        }
        return meta, {"rows": np.asarray(self.rows)}

    @classmethod
    def from_blocks(cls, name: str, meta: dict, arrays: dict) -> "TupleRelation":
        rows = jnp.asarray(np.asarray(arrays["rows"], np.int32))
        return cls(name, int(meta["arity"]), rows, int(meta["count"]),
                   int(meta["domain"]))


@functools.partial(jax.jit, static_argnames=("capacity", "domain"))
def _merge_sorted(a: jax.Array, b: jax.Array, capacity: int, domain: int) -> jax.Array:
    """Merge two sorted tables into one sorted ``capacity`` table.

    Compact-key path: sort the two tables' keys together (one single-operand
    sort, pads last), pad or cut to ``capacity`` and unpack the rows from
    the keys arithmetically.  The key is a bijection on rows over
    ``[0, domain)`` (a table whose values may leave it has ``domain`` 0 and
    sorts whole rows), so this is the exact merge, duplicates included.
    It touches no row by index: on the TPU a per-element gather or scatter
    over the big table (a ``searchsorted`` of every table row into the
    delta, then a row scatter) costs seconds where the sort streams.  This
    is the serving hot path: one merge per IDB per iteration, over tables
    that dwarf the delta.  Callers guarantee that the valid rows fit in
    ``capacity``.
    """
    with jax.named_scope(MERGE_SCOPE):
        ka = compact_key(a, domain)
        kb = compact_key(b, domain)
        if ka is None or kb is None:
            rows = jnp.concatenate([a, b], axis=0)
            if rows.shape[0] < capacity:
                pad = jnp.full(
                    (capacity - rows.shape[0], rows.shape[1]), SENTINEL, jnp.int32
                )
                rows = jnp.concatenate([rows, pad], axis=0)
            order = lexsort_rows(rows)
            return rows[order][:capacity]
        key = jnp.sort(jnp.concatenate([ka, kb]).astype(jnp.int32))
        if key.shape[0] < capacity:
            pad = jnp.full((capacity - key.shape[0],), SENTINEL, jnp.int32)
            key = jnp.concatenate([key, pad])
        return decode_key(key[:capacity], a.shape[1], domain)


@dataclass
class DenseSetRelation:
    """Unary recursive IDB as a boolean membership vector (REACH)."""

    name: str
    n: int
    member: jax.Array        # bool[n]
    delta: jax.Array         # bool[n] — newly added last iteration
    count: int = 0
    delta_count: int = 0

    @classmethod
    def empty(cls, name: str, n: int):
        z = jnp.zeros((n,), bool)
        return cls(name, n, z, z, 0, 0)

    def update(self, candidate_keys: jax.Array, valid: jax.Array) -> "DenseSetRelation":
        """Insert candidates; Δ = candidates not already members."""
        keys = jnp.where(valid, candidate_keys, 0)
        hit = jnp.zeros((self.n,), bool).at[keys].max(valid)
        delta = hit & ~self.member
        member = self.member | delta
        return DenseSetRelation(
            self.name,
            self.n,
            member,
            delta,
            int(member.sum()),
            int(delta.sum()),
        )

    def delete(
        self, candidate_keys: jax.Array, valid: jax.Array
    ) -> "DenseSetRelation":
        """Remove candidates; ``delta`` holds the keys actually removed (∇R).

        The bit-vector has no derivation counts, so a dense-set deletion is
        only sound as part of a full recompute or a DRed over-deletion pass —
        the serving layer taints the stratum non-monotone and recomputes.
        """
        ok = valid & (candidate_keys >= 0) & (candidate_keys < self.n)
        keys = jnp.where(ok, candidate_keys, 0)
        hit = jnp.zeros((self.n,), bool).at[keys].max(ok)
        removed = hit & self.member
        member = self.member & ~removed
        return DenseSetRelation(
            self.name,
            self.n,
            member,
            removed,
            int(member.sum()),
            int(removed.sum()),
        )

    def delta_tuples(self, capacity: int) -> tuple[jax.Array, int]:
        """Materialize Δ as a (capacity, 1) tuple view for the join machinery."""
        keys = jnp.where(self.delta, jnp.arange(self.n), SENTINEL)
        order = jnp.argsort(keys)
        rows = keys[order][:capacity, None].astype(jnp.int32)
        return rows, self.delta_count

    def device_buffers(self) -> tuple[jax.Array, ...]:
        """Device arrays owned by this handle (reclamation accounting)."""
        return (self.member, self.delta)

    def to_numpy(self) -> np.ndarray:
        return np.flatnonzero(np.asarray(self.member)).astype(np.int32)[:, None]

    def to_blocks(self) -> tuple[dict, dict[str, np.ndarray]]:
        """(meta, arrays) for the snapshot codec.

        ``delta`` is live state (a mid-fixpoint checkpoint resumes from it),
        so it is serialized alongside the membership vector; both are packed
        to bits on disk (``np.packbits``) — 8× smaller than bool arrays.
        """
        meta = {"kind": "dense_set", "n": self.n}
        return meta, {
            "member": np.packbits(np.asarray(self.member)),
            "delta": np.packbits(np.asarray(self.delta)),
        }

    @classmethod
    def from_blocks(cls, name: str, meta: dict, arrays: dict) -> "DenseSetRelation":
        n = int(meta["n"])
        member = jnp.asarray(
            np.unpackbits(np.asarray(arrays["member"]), count=n).astype(bool)
        )
        delta = jnp.asarray(
            np.unpackbits(np.asarray(arrays["delta"]), count=n).astype(bool)
        )
        return cls(name, n, member, delta, int(member.sum()), int(delta.sum()))


@dataclass
class DenseAggRelation:
    """Recursive MIN/MAX aggregate IDB as a dense best-value table (CC/SSSP)."""

    name: str
    n: int
    op: str                  # "MIN" | "MAX"
    values: jax.Array        # int32[n]; INT_INF (MIN) / -INT_INF (MAX) = absent
    delta: jax.Array         # bool[n] — keys improved last iteration
    count: int = 0
    delta_count: int = 0

    @property
    def absent(self) -> int:
        return INT_INF if self.op == "MIN" else -INT_INF

    @classmethod
    def empty(cls, name: str, n: int, op: str):
        absent = INT_INF if op == "MIN" else -INT_INF
        return cls(
            name,
            n,
            op,
            jnp.full((n,), absent, jnp.int32),
            jnp.zeros((n,), bool),
            0,
            0,
        )

    def update(
        self, candidate_keys: jax.Array, candidate_vals: jax.Array, valid: jax.Array
    ) -> "DenseAggRelation":
        keys = jnp.where(valid, candidate_keys, 0)
        if self.op == "MIN":
            vals = jnp.where(valid, candidate_vals, INT_INF)
            best = jnp.full((self.n,), INT_INF, jnp.int32).at[keys].min(vals)
            improved = best < self.values
            values = jnp.minimum(self.values, best)
        else:
            vals = jnp.where(valid, candidate_vals, -INT_INF)
            best = jnp.full((self.n,), -INT_INF, jnp.int32).at[keys].max(vals)
            improved = best > self.values
            values = jnp.maximum(self.values, best)
        return DenseAggRelation(
            self.name,
            self.n,
            self.op,
            values,
            improved,
            int((values != self.absent).sum()),
            int(improved.sum()),
        )

    def delete(
        self, candidate_keys: jax.Array, candidate_vals: jax.Array, valid: jax.Array
    ) -> "DenseAggRelation":
        """Remove ``(key, value)`` pairs whose value matches the stored best.

        Dropping a MIN/MAX winner is non-monotone: the displaced runner-up is
        not recoverable from the dense table (only the best value per key is
        kept), so the serving layer treats any dense-agg deletion as tainting
        the stratum — this method clears the keys and reports them in
        ``delta`` (∇R) so the caller can recompute and re-derive.
        """
        # out-of-range keys cannot name a stored pair — mask them out rather
        # than clip (clipping would let key n-1+k with a matching value
        # silently clear key n-1)
        ok = valid & (candidate_keys >= 0) & (candidate_keys < self.n)
        keys = jnp.where(ok, candidate_keys, 0)
        match = ok & (self.values[keys] == candidate_vals)
        removed = jnp.zeros((self.n,), bool).at[keys].max(match)
        values = jnp.where(removed, self.absent, self.values)
        return DenseAggRelation(
            self.name,
            self.n,
            self.op,
            values,
            removed,
            int((values != self.absent).sum()),
            int(removed.sum()),
        )

    def delta_tuples(self, capacity: int) -> tuple[jax.Array, int]:
        keys = jnp.where(self.delta, jnp.arange(self.n), SENTINEL)
        order = jnp.argsort(keys)
        srt = keys[order][:capacity].astype(jnp.int32)
        vals = jnp.where(
            srt != SENTINEL, self.values[jnp.minimum(srt, self.n - 1)], SENTINEL
        )
        return jnp.stack([srt, vals], axis=1), self.delta_count

    def full_tuples(self, capacity: int) -> tuple[jax.Array, int]:
        present = self.values != self.absent
        keys = jnp.where(present, jnp.arange(self.n), SENTINEL)
        order = jnp.argsort(keys)
        srt = keys[order][:capacity].astype(jnp.int32)
        vals = jnp.where(
            srt != SENTINEL, self.values[jnp.minimum(srt, self.n - 1)], SENTINEL
        )
        return jnp.stack([srt, vals], axis=1), self.count

    def device_buffers(self) -> tuple[jax.Array, ...]:
        """Device arrays owned by this handle (reclamation accounting)."""
        return (self.values, self.delta)

    def to_numpy(self) -> np.ndarray:
        vals = np.asarray(self.values)
        keys = np.flatnonzero(vals != self.absent)
        return np.stack([keys, vals[keys]], axis=1).astype(np.int32)

    def to_blocks(self) -> tuple[dict, dict[str, np.ndarray]]:
        """(meta, arrays) for the snapshot codec."""
        meta = {"kind": "dense_agg", "n": self.n, "op": self.op}
        return meta, {
            "values": np.asarray(self.values),
            "delta": np.packbits(np.asarray(self.delta)),
        }

    @classmethod
    def from_blocks(cls, name: str, meta: dict, arrays: dict) -> "DenseAggRelation":
        n = int(meta["n"])
        values = jnp.asarray(np.asarray(arrays["values"], np.int32))
        delta = jnp.asarray(
            np.unpackbits(np.asarray(arrays["delta"]), count=n).astype(bool)
        )
        h = cls(name, n, str(meta["op"]), values, delta)
        h.count = int((values != h.absent).sum())
        h.delta_count = int(delta.sum())
        return h


def relation_to_blocks(handle) -> tuple[dict, dict[str, np.ndarray]]:
    """Serialize any relation handle to (meta, arrays) — codec entry point."""
    fn = getattr(handle, "to_blocks", None)
    if fn is None:
        raise TypeError(f"{type(handle).__name__} is not serializable")
    return fn()


def relation_from_blocks(name: str, meta: dict, arrays: dict):
    """Rebuild a relation handle from codec (meta, arrays)."""
    kinds = {
        "tuple": TupleRelation,
        "dense_set": DenseSetRelation,
        "dense_agg": DenseAggRelation,
    }
    kind = meta.get("kind")
    if kind not in kinds:
        raise ValueError(f"unknown relation kind {kind!r} for {name!r}")
    return kinds[kind].from_blocks(name, meta, arrays)
