"""Sorted-table primitives: the TPU-native replacement for hash tables.

RecStep's FAST-DEDUP builds a latch-free chaining hash table over a *Compact
Concatenated Key* (CCK): the tuple packed into a single machine word, used both
as the key and as its own hash.  A TPU has no latch-free hash tables, so we
keep the CCK idea (pack the tuple into one word when the active domain allows)
but swap the container: **sort + adjacent-unique**, which is the efficient
dedup/bulk-lookup primitive on a vector unit.

Relations are ``int32[capacity, arity]`` with valid rows in ``[0, count)`` and
pad rows filled with ``SENTINEL`` so that a full-table sort keeps padding at
the end.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Largest int32.  All domain values must be < SENTINEL.
SENTINEL = jnp.iinfo(jnp.int32).max


def compact_key_fits(arity: int, domain: int) -> bool:
    """Whether ``arity``-column rows over ``domain`` pack into one key."""
    return arity == 1 or (0 < domain and domain ** arity < SENTINEL)


def compact_key(rows: jax.Array, domain: int) -> jax.Array | None:
    """Pack an ``int32[n, k]`` tuple table into a single ``int32[n]`` key.

    Returns ``None`` when ``domain ** arity`` does not fit in 31 bits — the
    caller falls back to lexicographic multi-key sorting, mirroring the
    paper's note that the CCK applies when attribute widths are small.
    Padding rows map to SENTINEL (all-SENTINEL rows stay maximal).
    """
    arity = rows.shape[1]
    if not compact_key_fits(arity, domain):
        return None
    if arity == 1:
        return rows[:, 0]
    key = rows[:, 0]
    for c in range(1, arity):
        key = key * domain + rows[:, c]
    # Remap pads: any row containing SENTINEL is padding.
    is_pad = jnp.any(rows == SENTINEL, axis=1)
    return jnp.where(is_pad, SENTINEL, key)


def decode_key(key: jax.Array, arity: int, domain: int) -> jax.Array:
    """Inverse of :func:`compact_key`: ``int32[n]`` keys → ``int32[n, arity]``.

    Valid keys unpack by repeated ``divmod`` by ``domain``; a SENTINEL key
    becomes an all-SENTINEL pad row.
    """
    if arity == 1:
        return key[:, None]
    cols = []
    rest = key
    for _ in range(arity - 1):
        cols.append(rest % domain)
        rest = rest // domain
    cols.append(rest)
    rows = jnp.stack(cols[::-1], axis=1)
    return jnp.where((key == SENTINEL)[:, None], SENTINEL, rows)


def lexsort_rows(rows: jax.Array) -> jax.Array:
    """Permutation sorting rows lexicographically (first column primary)."""
    keys = tuple(rows[:, c] for c in range(rows.shape[1] - 1, -1, -1))
    return jnp.lexsort(keys)


def sort_rows(rows: jax.Array, domain: int = 0) -> jax.Array:
    """Sort a tuple table lexicographically, pads last.

    Uses the compact key single-sort fast path when the domain allows
    (FAST-DEDUP's CCK), otherwise lexsort.
    """
    key = compact_key(rows, domain)
    if key is not None:
        order = jnp.argsort(key)
    else:
        order = lexsort_rows(rows)
    return rows[order]


def unique_mask(sorted_rows: jax.Array) -> jax.Array:
    """``bool[n]`` marking the first occurrence of each distinct valid row.

    Input must be row-sorted.  Padding rows (all-SENTINEL) are masked out.
    """
    neq_prev = jnp.any(sorted_rows[1:] != sorted_rows[:-1], axis=1)
    first = jnp.concatenate([jnp.ones((1,), dtype=bool), neq_prev])
    valid = sorted_rows[:, 0] != SENTINEL
    return first & valid


def searchsorted_rows(
    sorted_key: jax.Array, probe_key: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(lo, hi) ranges of ``probe_key`` values within ``sorted_key``."""
    lo = jnp.searchsorted(sorted_key, probe_key, side="left")
    hi = jnp.searchsorted(sorted_key, probe_key, side="right")
    return lo, hi


@functools.partial(jax.jit, static_argnames=("capacity",))
def expand_matches(
    lo: jax.Array, counts: jax.Array, capacity: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Vectorized join-match expansion.

    Given per-probe match ranges ``[lo, lo+counts)`` in the build side,
    produce for each output slot ``t`` in ``[0, capacity)``:
      * ``probe_idx[t]``  — which probe row produced slot t,
      * ``build_idx[t]``  — which build row it matched,
      * ``valid[t]``      — slot holds a real match (t < total).
    Standard offsets+searchsorted expansion; total is data-dependent but the
    output shape is static (capacity), with a mask.
    """
    offsets = jnp.cumsum(counts)                     # inclusive
    total = offsets[-1] if counts.size else jnp.int32(0)
    slots = jnp.arange(capacity, dtype=counts.dtype)
    probe_idx = jnp.searchsorted(offsets, slots, side="right")
    probe_idx = jnp.minimum(probe_idx, counts.shape[0] - 1)
    excl = offsets[probe_idx] - counts[probe_idx]    # exclusive offset
    within = slots - excl
    build_idx = lo[probe_idx] + within
    valid = slots < total
    # Clamp to keep gathers in-bounds; invalid slots are masked by callers.
    build_idx = jnp.where(valid, build_idx, 0)
    probe_idx = jnp.where(valid, probe_idx, 0)
    return probe_idx, build_idx, valid
