"""Public jit'd wrappers for the Pallas kernels.

Each op pads its inputs to the kernel's tile grid, dispatches to the Pallas
implementation, and un-pads the result.  The backend decides how the kernel
runs (:func:`interpret_mode`): compiled by Mosaic on a TPU, in the Pallas
interpreter on the CPU (the validation path the tests use), and on any other
backend not at all.  ``ref.py`` holds the pure-jnp oracles the tests compare
against.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels import bitmm as _bitmm
from repro.kernels import gather_sum as _gather

WORD = 32
TILE = 128


def interpret_mode() -> bool:
    """False on a TPU (Mosaic compiles the kernel), True on the CPU (the
    Pallas interpreter runs it); any other backend is an error."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"the default backend is {backend!r}"
    )


def _pad2(x: jax.Array, r: int, c: int, value=0) -> jax.Array:
    pr, pc = (-x.shape[0]) % r, (-x.shape[1]) % c
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)), constant_values=value)
    return x


def _pad_bitmm(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Pad A to (TM-row, tiled-word) shape and B to A's padded K bits.
    Zero bits are absorbing for OR-AND, so padding never changes C."""
    a_p = _pad2(a, _bitmm.TM, 1)
    kw = _bitmm.padded_words(max(a.shape[1], -(-b.shape[0] // WORD)))
    a_p = _pad2(a_p, 1, kw)
    b_p = _pad2(b, kw * WORD, 1)
    b_p = _pad2(b_p, 1, _bitmm.padded_words(b.shape[1]))
    return a_p, b_p


def bitmm(a: jax.Array, b: jax.Array, n: int | None = None) -> jax.Array:
    """Boolean matmul on bit-packed uint32 operands (PBME hot loop).

    a: uint32[M, Kw], b: uint32[K, Nw] with K = Kw*32.  Arbitrary sizes —
    padded to the kernel's tile grid.
    """
    m0, _ = a.shape
    _, nw0 = b.shape
    a_p, b_p = _pad_bitmm(a, b)
    out = _bitmm.bitmm_call(a_p, b_p, interpret=interpret_mode())
    return out[:m0, :nw0]


def bitmm_fused_delta(
    a: jax.Array, b: jax.Array, m_cur: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Fused PBME iteration: (Δ', M') = ((A⊛B) & ~M, M | Δ')."""
    m0, _ = a.shape
    _, nw0 = b.shape
    a_p, b_p = _pad_bitmm(a, b)
    m_p = _pad2(m_cur, a_p.shape[0], b_p.shape[1])
    delta, m_new = _bitmm.bitmm_fused_delta_call(
        a_p, b_p, m_p, interpret=interpret_mode()
    )
    return delta[:m0, :nw0], m_new[:m0, :nw0]


def spmm_ell(idx: jax.Array, x: jax.Array) -> jax.Array:
    """ELL SpMM: out[i] = Σ_k x[idx[i,k]] (pad = -1).  GNN aggregation."""
    d0 = x.shape[1]
    x_p = _pad2(x, 1, TILE)
    out = _gather.gather_sum_call(idx, x_p, interpret=interpret_mode())
    return out[:, :d0]


def embed_bag(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Embedding-bag: out[b] = Σ_k table[idx[b,k]] (pad = -1).  RecSys."""
    return spmm_ell(idx, table)
