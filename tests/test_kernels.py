"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

On the CPU every kernel runs in the Pallas interpreter
(``ops.interpret_mode``) — the correctness contract for the TPU target;
``test_tpu_compile.py`` checks that the TPU compiler accepts them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _pack(dense: np.ndarray) -> jnp.ndarray:
    r, c = dense.shape
    pad = (-c) % 32
    d2 = np.pad(dense, ((0, 0), (0, pad))).astype(np.uint32).reshape(r, -1, 32)
    return jnp.asarray(
        (d2 << np.arange(32, dtype=np.uint32)).sum(axis=-1, dtype=np.uint32)
    )


BITMM_SHAPES = [
    (128, 128, 128),
    (256, 128, 384),
    (128, 256, 128),
    (384, 384, 256),
    (130, 70, 200),      # unaligned — exercises tile padding
    (64, 33, 97),
]


@pytest.mark.parametrize("shape", BITMM_SHAPES)
@pytest.mark.parametrize("density", [0.02, 0.3])
def test_bitmm_sweep(shape, density):
    m, k, n = shape
    rng = np.random.default_rng(m * 7 + k + n)
    a = rng.random((m, k)) < density
    b = rng.random((k, n)) < density
    got_packed = ops.bitmm(_pack(a), _pack(b))
    got = np.asarray(ref.unpack_bits(got_packed))[:, :n] > 0
    expect = (a.astype(np.int64) @ b.astype(np.int64)) > 0
    assert (got == expect).all()


@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 128, 256), (100, 50, 130)])
def test_bitmm_fused_delta_sweep(shape):
    m, k, n = shape
    rng = np.random.default_rng(0)
    a = rng.random((m, k)) < 0.1
    b = rng.random((k, n)) < 0.1
    cur = rng.random((m, n)) < 0.05
    delta, m_new = ops.bitmm_fused_delta(_pack(a), _pack(b), _pack(cur))
    new = (a.astype(np.int64) @ b.astype(np.int64)) > 0
    exp_delta = new & ~cur
    exp_m = cur | exp_delta
    got_delta = np.asarray(ref.unpack_bits(delta))[:, :n] > 0
    got_m = np.asarray(ref.unpack_bits(m_new))[:, :n] > 0
    assert (got_delta == exp_delta).all()
    assert (got_m == exp_m).all()


@pytest.mark.parametrize("shape", [(128, 8192, 8192), (130, 9000, 4200)])
def test_bitmm_word_tiled(shape):
    """Past ``FULL_MAX`` words the kernel tiles K and N in 128-word blocks;
    the accumulator must carry across K blocks and planes."""
    m, k, n = shape
    rng = np.random.default_rng(k)
    a = rng.random((m, k)) < 0.0005
    b = rng.random((k, n)) < 0.0005
    cur = rng.random((m, n)) < 0.0005
    new = (a.astype(np.float32) @ b.astype(np.float32)) > 0
    got = np.asarray(ref.unpack_bits(ops.bitmm(_pack(a), _pack(b))))[:, :n] > 0
    assert new.any() and (got == new).all()
    delta, m_new = ops.bitmm_fused_delta(_pack(a), _pack(b), _pack(cur))
    got_delta = np.asarray(ref.unpack_bits(delta))[:, :n] > 0
    got_m = np.asarray(ref.unpack_bits(m_new))[:, :n] > 0
    assert (got_delta == (new & ~cur)).all()
    assert (got_m == (cur | new)).all()


def test_interpret_mode_follows_backend(monkeypatch):
    assert ops.interpret_mode() is True             # CPU: the interpreter
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode() is False            # TPU: Mosaic compiles
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bk", [(8, 3, 20, 128), (16, 7, 50, 256), (4, 1, 5, 384)])
def test_gather_sum_sweep(dtype, bk):
    b, k, n, d = bk
    rng = np.random.default_rng(b + k)
    idx = rng.integers(-1, n, size=(b, k)).astype(np.int32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    xj = jnp.asarray(x, dtype)
    got = ops.spmm_ell(jnp.asarray(idx), xj)
    expect = ref.spmm_ell(jnp.asarray(idx), xj)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expect, np.float32), atol=tol, rtol=tol
    )


def test_embed_bag_matches_relational_reference():
    from repro.relational.embedding import embedding_bag

    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((40, 128)).astype(np.float32))
    idx = jnp.asarray(rng.integers(-1, 40, size=(6, 5)).astype(np.int32))
    got = ops.embed_bag(table, idx)
    expect = embedding_bag(table, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-5)


def test_bitmm_empty_and_full():
    z = jnp.zeros((128, 4), jnp.uint32)
    f = jnp.full((128, 4), 0xFFFFFFFF, jnp.uint32)
    assert int(ops.bitmm(z, z).sum()) == 0
    out = ops.bitmm(f, f)
    assert (np.asarray(out) == 0xFFFFFFFF).all()


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(2)
    dense = rng.random((64, 96)) < 0.5
    packed = ref.pack_bits(jnp.asarray(dense.astype(np.float32)))
    back = np.asarray(ref.unpack_bits(packed)) > 0
    assert (back[:, :96] == dense).all()
