"""Pallas kernel correctness in INTERPRET mode on the CPU mesh: this
checks the kernels' arithmetic and indexing only. That the installed
Mosaic compiler accepts them (interpret=False, at the store's row width)
is checked on the chip by `chip_smoke.py`, part "kernels"."""
import numpy as np
import pytest

import jax.numpy as jnp


def test_gather_rows_matches_xla():
    from adapm_tpu.ops.pallas_kernels import gather_rows
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(128, 256)).astype(np.float32))
    block_rows = 8
    idx = jnp.asarray(rng.integers(0, 128 // block_rows, 10)
                      .astype(np.int32))
    got = gather_rows(pool, idx, block_rows=block_rows, interpret=True)
    ref = np.asarray(pool).reshape(-1, block_rows, 256)[
        np.asarray(idx)].reshape(-1, 256)
    assert np.allclose(np.asarray(got), ref)


def test_adagrad_apply_matches_numpy():
    from adapm_tpu.ops.pallas_kernels import adagrad_apply
    rng = np.random.default_rng(1)
    n, L = 512, 128
    g = rng.normal(size=(n, L)).astype(np.float32)
    emb = rng.normal(size=(n, L)).astype(np.float32)
    acc = np.abs(rng.normal(size=(n, L))).astype(np.float32)
    lr, eps = 0.1, 1e-10
    new_emb, new_acc = adagrad_apply(jnp.asarray(g), jnp.asarray(emb),
                                     jnp.asarray(acc), lr, eps,
                                     interpret=True)
    ref_acc = acc + g * g
    ref_emb = emb - lr * g / np.sqrt(ref_acc + eps)
    assert np.allclose(np.asarray(new_acc), ref_acc, rtol=1e-5)
    assert np.allclose(np.asarray(new_emb), ref_emb, rtol=1e-4, atol=1e-6)
