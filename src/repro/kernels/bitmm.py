"""Pallas TPU kernel: boolean bit-matrix matmul (the PBME hot loop).

The paper's PBME evaluates TC/SG by per-row scalar worklists over a bit
matrix — a MIMD-thread design.  The TPU-native adaptation runs the same
boolean-semiring product on the MXU:

  * operands stay **bit-packed in HBM/VMEM** (uint32, 32 bits/word) — 8×
    less HBM traffic than bytes, 32× less than f32;
  * bits are **unpacked in-register** one shift at a time to {0,1} bf16,
    multiplied on the MXU with f32 accumulation (counts ≤ K fit exactly),
    thresholded, and **re-packed** before the store;
  * the semi-naïve epilogue (Δ' = New & ~M; M' = M | Δ') is **fused** into
    the same kernel, so dedup + set-difference never touch HBM as dense data.

Layout.  Packed matrices are ``uint32[rows, words]`` with bit j of word w
holding column 32w + j (``core/bitmatrix.py``).  Unpacking a word block into
bit columns in that order would interleave across lanes, which Mosaic cannot
lower, so the kernel never does it.  Instead it works one *bit plane* at a
time: ``(x >> j) & 1`` over a word block is the {0,1} sub-matrix of columns
{32w + j}.  The contraction runs over (word block, plane jk): plane jk of A
meets rows {32w + jk} of B, which the wrapper presents as the plane-major view
``B[w*32 + jk] → Bp[jk, w]`` (one XLA transpose of the word array).  The
output keeps one f32 accumulator per output plane jn, and the epilogue ORs
``(acc[jn] > 0) << jn`` back into words.  Only elementwise ops on whole
tiles, 2-D dots and leading-dim scratch indexing remain.

Tiling: grid (M/TM, Nw/TW, Kw/TW, 32); every block's last two dims are
multiples of (8, 128) or span the whole array (word counts ≤ ``FULL_MAX``),
which is what the TPU compiler requires.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD = 32
TM = 128          # output row tile
TW = 128          # word tile (4096 bits) along K and N when the word count allows
FULL_MAX = 256    # word counts up to this run as one full-width block
#: The device scope of the kernels (``jax.named_scope``, op metadata only).
PBME_SCOPE = "pbme"


def padded_words(words: int) -> int:
    """The word count the kernel tiles for ``words`` words: itself up to one
    full-width block, else the next multiple of ``TW``."""
    if words <= FULL_MAX:
        return words
    return -(-words // TW) * TW


def word_tile(words: int) -> int:
    """The word-block width for a packed dimension of ``words`` words."""
    if padded_words(words) != words:
        raise ValueError(
            f"{words} words: pad to a multiple of {TW} (or ≤ {FULL_MAX}) first"
        )
    return TW if words % TW == 0 else words


def _plane(words: jax.Array, shift) -> jax.Array:
    """{0,1} bf16 plane ``shift`` of a word block (column w ↔ bit 32w+shift)."""
    bit = (words >> shift) & jnp.uint32(1)
    return jnp.where(bit != 0, 1.0, 0.0).astype(jnp.bfloat16)


def _accumulate(a_ref, b_ref, acc_ref):
    """acc[jn] += plane_jk(A) @ plane_jn(B rows of plane jk), for all jn."""
    jk = pl.program_id(3)

    @pl.when((pl.program_id(2) == 0) & (jk == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = _plane(a_ref[...], jk.astype(jnp.uint32))      # (TM, TKW)
    b_words = b_ref[...]                               # (TKW, TNW)

    def body(jn, carry):
        b = _plane(b_words, jn.astype(jnp.uint32))
        acc_ref[jn] += jax.lax.dot(a, b, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, WORD, body, 0)


def _is_last_step():
    return (pl.program_id(2) == pl.num_programs(2) - 1) & (
        pl.program_id(3) == WORD - 1
    )


def _pack(acc_ref) -> jax.Array:
    """uint32 words from the per-plane counts: bit jn ← acc[jn] > 0."""

    def body(jn, words):
        bit = jnp.uint32(1) << jn.astype(jnp.uint32)
        return words | jnp.where(acc_ref[jn] > 0.0, bit, jnp.uint32(0))

    init = jnp.zeros(acc_ref.shape[1:], jnp.uint32)
    return jax.lax.fori_loop(0, WORD, body, init)


def _bitmm_kernel(a_ref, b_ref, c_ref, acc_ref):
    _accumulate(a_ref, b_ref, acc_ref)

    @pl.when(_is_last_step())
    def _done():
        c_ref[...] = _pack(acc_ref)


def _bitmm_fused_kernel(a_ref, b_ref, m_ref, delta_ref, mout_ref, acc_ref):
    _accumulate(a_ref, b_ref, acc_ref)

    @pl.when(_is_last_step())
    def _done():
        new = _pack(acc_ref)
        m = m_ref[...]
        delta = new & ~m                              # DSD fused: andnot
        delta_ref[...] = delta
        mout_ref[...] = m | delta                     # merge fused: or


def _call(kernel, a, b, extra, n_out, interpret):
    """Shared pallas_call plumbing: A row/word blocks, plane-major B blocks,
    ``extra`` inputs and ``n_out`` outputs tiled like C."""
    m, kw = a.shape
    k, nw = b.shape
    assert kw * WORD == k, (a.shape, b.shape)
    assert m % TM == 0, a.shape
    tk, tn = word_tile(kw), word_tile(nw)
    # plane-major B: bp[j, w] = b[32w + j] — row set of bit plane j of A
    bp = b.reshape(kw, WORD, nw).transpose(1, 0, 2)
    c_spec = pl.BlockSpec((TM, tn), lambda i, j, kk, p: (i, j))
    return pl.pallas_call(
        kernel,
        grid=(m // TM, nw // tn, kw // tk, WORD),
        in_specs=[
            pl.BlockSpec((TM, tk), lambda i, j, kk, p: (i, kk)),
            pl.BlockSpec((None, tk, tn), lambda i, j, kk, p: (p, kk, j)),
        ]
        + [c_spec] * len(extra),
        out_specs=c_spec if n_out == 1 else [c_spec] * n_out,
        out_shape=(
            jax.ShapeDtypeStruct((m, nw), jnp.uint32)
            if n_out == 1
            else [jax.ShapeDtypeStruct((m, nw), jnp.uint32)] * n_out
        ),
        scratch_shapes=[pltpu.VMEM((WORD, TM, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="bitmm",
    )(a, bp, *extra)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmm_call(a: jax.Array, b: jax.Array, *, interpret: bool) -> jax.Array:
    """C = A ⊛ B on packed operands.

    a: uint32[M, Kw]; b: uint32[Kw*32, Nw]; M a multiple of ``TM``; Kw and
    Nw multiples of ``TW`` or at most ``FULL_MAX``.
    """
    with jax.named_scope(PBME_SCOPE):
        return _call(_bitmm_kernel, a, b, (), 1, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmm_fused_delta_call(
    a: jax.Array, b: jax.Array, m_cur: jax.Array, *, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    """One fused PBME iteration: (Δ', M') = ((A⊛B) & ~M, M | Δ')."""
    assert m_cur.shape == (a.shape[0], b.shape[1]), (a.shape, b.shape, m_cur.shape)
    with jax.named_scope(PBME_SCOPE):
        return _call(_bitmm_fused_kernel, a, b, (m_cur,), 2, interpret)
