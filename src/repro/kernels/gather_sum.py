"""Pallas TPU kernel: scalar-prefetched gather-sum (ELL SpMM / embedding-bag).

One kernel serves two hot paths that are the *same relational op*:

  * GNN neighbor aggregation over a padded (ELL) neighbor list —
    ``out[i] = Σ_k X[idx[i, k]]``;
  * recsys embedding-bag — ``out[b] = Σ_k table[idx[b, k]]``.

TPU adaptation: the source matrix stays in **HBM**; the index matrix is a
**scalar-prefetch** operand so the BlockSpec ``index_map`` can steer the
HBM→VMEM DMA for each grid step (the canonical Pallas gather pattern — the
gather itself becomes the block fetch, there is no in-kernel random access).
Grid (B, K): step (b, k) fetches row ``idx[b, k]`` of X into VMEM and
accumulates it into out row b; pad slots (idx < 0) are masked, clamped to row
0 for the fetch.

The feature dim D is the VMEM tile width; rows are (1, D) blocks (D multiple
of 128 for lane alignment).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _gather_sum_kernel(idx_ref, x_row_ref, out_ref):
    b = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    valid = idx_ref[b, k] >= 0
    row = x_row_ref[...]
    out_ref[...] += jnp.where(valid, row, jnp.zeros_like(row))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_sum_call(
    idx: jax.Array, x: jax.Array, *, interpret: bool
) -> jax.Array:
    """idx: int32[B, K] (-1 pad); x: f32[N, D] → f32[B, D] row sums."""
    bsz, k = idx.shape
    _, d = x.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, k),
        in_specs=[
            pl.BlockSpec(
                (1, d),
                lambda b, kk, idx_ref: (jnp.maximum(idx_ref[b, kk], 0), 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, d), lambda b, kk, idx_ref: (b, 0)),
    )
    return pl.pallas_call(
        _gather_sum_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, d), x.dtype),
        interpret=interpret,
    )(idx, x)
