"""Matrix factorization with AdaGrad + L2 (reference
apps/matrix_factorization.cc
+ apps/mf/update.h:23-79 `UpdateNsqlL2Adagrad`).

Key layout (matrix_factorization.cc:692-697): row keys [0, first_col_key),
column keys from first_col_key; value row = [factor (rank) | AdaGrad (rank)].
Loss = nonzero squared loss + L2 on both factors.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def make_mf_loss(l2: float = 0.0):
    """Roles: w [B, rank] (row factors), h [B, rank] (col factors);
    aux = observed ratings x [B]. Mean squared residual + L2."""

    def loss_fn(embs, aux):
        w, h = embs["w"], embs["h"]
        x = aux
        pred = (w * h).sum(-1)
        err = (pred - x) ** 2
        reg = l2 * ((w * w).sum(-1) + (h * h).sum(-1))
        return (err + reg).mean()

    return loss_fn


def mf_sq_error(embs, aux):
    """The read-only objective of the pass-end loss (reference
    apps/mf/loss.h): the squared residuals of a batch, summed. Roles as
    in `make_mf_loss`; aux = (x [B], n): only the first n examples count
    (the last batch of a walk is filled up to B)."""
    x, n = aux
    err = ((embs["w"] * embs["h"]).sum(-1) - x) ** 2
    return jnp.where(jnp.arange(err.shape[0]) < n, err, 0).sum()


def row_key(i: np.ndarray):
    return np.asarray(i, dtype=np.int64)


def col_key(j: np.ndarray, first_col_key: int):
    return np.asarray(j, dtype=np.int64) + first_col_key


def full_loss(W: np.ndarray, H: np.ndarray, coo, l2: float = 0.0) -> float:
    """Test/train loss over all observed entries (reference apps/mf/loss.h):
    coo = (rows, cols, vals) numpy arrays."""
    i, j, x = coo
    pred = (W[i] * H[j]).sum(-1)
    err = float(((pred - x) ** 2).sum())
    if l2:
        err += l2 * float((W * W).sum() + (H * H).sum())
    return err
