"""Distributed PBME: the paper's zero-coordination row partitioning on a mesh.

RecStep partitions bit-matrix rows round-robin across CPU threads with "no or
nearly no coordination" (§5.3).  The multi-chip analogue is a 2-D SUMMA-style
decomposition:

  * Δ and M row-sharded over the data-parallel axes (``pod``, ``data``) —
    each chip owns a row block, exactly the paper's partitioning;
  * Arc column-sharded over ``model`` — the closure's columns spread across
    the tensor axis;
  * one iteration = **one all-gather of Δ along ``model``** (rebuild full Δ
    rows) + a purely local boolean matmul + local andnot/or epilogue + a
    psum'd popcount for the termination test.

The all-gather is the only collective; its bytes are |Δ_rows|·n/8 per chip
per iteration — reported in the roofline.  SG's work-stealing coordination
(SG-PBME-COORD) does not transfer to TPU; skew is instead absorbed
statistically by 2-D sharding (see DESIGN.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.bitmatrix import bitmm_ref, edges_to_bitmatrix, unpack_bits

WORD = 32


def _popcount_u32(x: jax.Array) -> jax.Array:
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32).sum()


def padded_n(n: int, row_shards: int, col_shards: int) -> int:
    """Pad the domain so row blocks tile by 128 and col blocks by 128 bits."""
    row_q = 128 * row_shards
    col_q = 128 * col_shards
    q = max(row_q, col_q)
    # lcm(row_q, col_q) both powers-of-two multiples of 128 → max works
    return ((n + q - 1) // q) * q


def make_tc_step(mesh: Mesh, row_axes: tuple[str, ...], col_axis: str):
    """Build the jitted sharded PBME-TC iteration for ``mesh``.

    State: delta, m  — uint32[n, n/32] sharded P(row_axes, col_axis);
           arc      — uint32[n, n/32] sharded P(None, col_axis).
    Returns (delta', m', popcount(delta')).
    """
    spec_dm = P(row_axes, col_axis)
    spec_arc = P(None, col_axis)

    def step(delta, arc, m):
        # rebuild full Δ rows: the single collective of the iteration
        delta_full = jax.lax.all_gather(delta, col_axis, axis=1, tiled=True)
        new = bitmm_ref(delta_full, arc, delta_full.shape[1] * WORD)
        d_new = new & ~m
        m_new = m | d_new
        cnt = jax.lax.psum(
            _popcount_u32(d_new), tuple(row_axes) + (col_axis,)
        )
        return d_new, m_new, cnt

    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=(spec_dm, spec_arc, spec_dm),
        out_specs=(spec_dm, spec_dm, P()),
        check_vma=False,
    )
    return jax.jit(sharded)


def tc_fixpoint_sharded(
    edges,
    n: int,
    mesh: Mesh,
    row_axes: tuple[str, ...] = ("data",),
    col_axis: str = "model",
    max_iters: int = 10_000,
):
    """Distributed transitive closure; returns (M packed on mesh, iterations)."""
    row_shards = 1
    for a in row_axes:
        row_shards *= mesh.shape[a]
    col_shards = mesh.shape[col_axis]
    n_pad = padded_n(n, row_shards, col_shards * WORD // WORD)
    arc_host = edges_to_bitmatrix(edges, n_pad)

    arc = jax.device_put(arc_host, NamedSharding(mesh, P(None, col_axis)))
    dm_sharding = NamedSharding(mesh, P(row_axes, col_axis))
    m = jax.device_put(arc_host, dm_sharding)
    delta = jax.device_put(arc_host, dm_sharding)

    step = make_tc_step(mesh, row_axes, col_axis)
    iters = 0
    while iters < max_iters:
        delta, m, cnt = step(delta, arc, m)
        iters += 1
        if int(cnt) == 0:
            break
    return m, n_pad, iters


def make_tc_step_1d(mesh: Mesh, row_axes: tuple[str, ...]):
    """PAPER-FAITHFUL schedule: pure row partitioning, Arc replicated.

    This is the direct translation of PBME's zero-coordination thread
    model (§5.3): every chip owns a row block of M/Δ and the WHOLE Arc, so
    one iteration needs NO collectives at all (only the popcount psum for
    termination).  The cost is Arc replication: n²/8 bytes per chip — fine
    to ~100k vertices on v5e, impossible at 1M+ (→ the 2-D schedule)."""
    spec_rows = P(row_axes, None)

    def step(delta, arc, m):
        new = bitmm_ref(delta, arc, arc.shape[0])
        d_new = new & ~m
        m_new = m | d_new
        cnt = jax.lax.psum(_popcount_u32(d_new), tuple(row_axes))
        return d_new, m_new, cnt

    return jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=(spec_rows, P(None, None), spec_rows),
            out_specs=(spec_rows, spec_rows, P()),
            check_vma=False,
        )
    )


def make_tc_step_psum(mesh: Mesh, row_axes: tuple[str, ...], col_axis: str):
    """Alternative 2-D schedule: contraction-dim sharding + reduce-scatter.

    Δ sharded (rows × k-cols), Arc sharded (k-rows × none): each chip
    computes a PARTIAL product over its k-slice, then a reduce-scatter
    (boolean OR ≡ integer max) assembles and re-shards New over columns.
    Collective moves New (counts) instead of Δ (bits) — wins when the
    frontier Δ is dense and New is small, loses otherwise; see §Perf."""
    spec_dm = P(row_axes, col_axis)
    spec_arc = P(col_axis, None)          # Arc k-rows sharded

    def step(delta, arc, m):
        # partial boolean matmul over the local k-slice (counts in f32)
        from repro.core.bitmatrix import unpack_bits, pack_bits

        a = unpack_bits(delta).astype(jnp.float32)
        b = unpack_bits(arc).astype(jnp.float32)
        partial = a @ b                                     # [rows_loc, n]
        summed = jax.lax.psum_scatter(
            partial, col_axis, scatter_dimension=1, tiled=True
        )
        new = pack_bits(summed > 0)
        d_new = new & ~m
        m_new = m | d_new
        cnt = jax.lax.psum(
            _popcount_u32(d_new), tuple(row_axes) + (col_axis,)
        )
        return d_new, m_new, cnt

    return jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=(spec_dm, spec_arc, spec_dm),
            out_specs=(spec_dm, spec_dm, P()),
            check_vma=False,
        )
    )


def lower_tc_step(
    mesh: Mesh,
    n: int,
    row_axes=("data",),
    col_axis="model",
    schedule: str = "allgather",
):
    """AOT lower the sharded TC step (dry-run / roofline / §Perf entry).

    schedule ∈ {"allgather" (2-D baseline), "rows1d" (paper-faithful),
    "psum" (reduce-scatter variant)}."""
    row_shards = 1
    for a in row_axes:
        row_shards *= mesh.shape[a]
    n_pad = padded_n(n, row_shards, mesh.shape[col_axis])
    w = n_pad // WORD
    sds = lambda spec: jax.ShapeDtypeStruct(
        (n_pad, w), jnp.uint32, sharding=NamedSharding(mesh, spec)
    )
    if schedule == "rows1d":
        step = make_tc_step_1d(mesh, tuple(row_axes))
        args = (sds(P(row_axes, None)), sds(P(None, None)), sds(P(row_axes, None)))
    elif schedule == "psum":
        step = make_tc_step_psum(mesh, tuple(row_axes), col_axis)
        dm = P(row_axes, col_axis)
        args = (sds(dm), sds(P(col_axis, None)), sds(dm))
    else:
        step = make_tc_step(mesh, tuple(row_axes), col_axis)
        dm = P(row_axes, col_axis)
        args = (sds(dm), sds(P(None, col_axis)), sds(dm))
    return step.lower(*args)
