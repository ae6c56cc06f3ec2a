"""Matrix factorization with the squared loss and L2, numpy float32.

A batch of B revealed cells (i_b, j_b, x_b) of a matrix; w_b is the factor
of row i_b, h_b the factor of column j_b, both of width `rank`:

    mean_b [ (<w_b, h_b> - x_b)^2 + l2 (|w_b|^2 + |h_b|^2) ]

(upstream apps/mf/update.h, UpdateNsqlL2Adagrad, taken over a batch). A
training step pushes, for every POSITION of the batch and from the rows as
they were before the step, the AdaGrad update of `adagrad_np` to the row
of w_b and to the row of h_b; positions that name one row add up, in batch
order (`step`). The loss over all revealed cells that the bold driver
reads at a pass end is `full_loss` (upstream apps/mf/loss.h).
"""
from __future__ import annotations

import numpy as np

from . import adagrad_np
from .complex_np import lower


def loss_and_grads(w, h, x, l2, dtype=np.float32, batch_size=None):
    """w, h: [B, rank]; x: [B]. Returns (loss, gradients per position:
    w, h [B, rank]). `dtype` other than float32 is the lower-precision
    control: inputs, predictions, residuals and gradients are rounded to
    it. `batch_size`, where the arrays are a block of a larger batch, is
    that batch's size: the block's share of the batch's mean loss comes
    back, and the blocks' shares add up to it."""
    low = lower(dtype)
    w, h = (low(a.astype(np.float32)) for a in (w, h))
    x = low(np.asarray(x, dtype=np.float32))
    B = np.float32(batch_size or w.shape[0])
    l2 = np.float32(l2)
    res = low(low((w * h).sum(-1, dtype=np.float32)) - x)
    reg = l2 * ((w * w).sum(-1, dtype=np.float32)
                + (h * h).sum(-1, dtype=np.float32))
    loss = (res * res + reg).sum(dtype=np.float64) / float(B)
    c = low(np.float32(2) * res / B)[:, None]
    g_w = c * h + (np.float32(2) * l2 / B) * w
    g_h = c * w + (np.float32(2) * l2 / B) * h
    return float(loss), {"w": low(g_w.astype(np.float32)),
                         "h": low(g_h.astype(np.float32))}


def step(table, wkeys, hkeys, x, l2, lr, dtype=np.float32):
    """One training step on `table` [keys, 2 rank] = [factor | AdaGrad],
    in place; returns the batch's loss. Every position's update is formed
    from the rows as they stand before the step (`position_updates`), and
    `np.add.at` adds them to their rows in batch order, row factors first:
    positions that name one row add up and do not see each other."""
    rank = table.shape[1] // 2
    rw, rh = table[wkeys], table[hkeys]
    loss, g = loss_and_grads(rw[:, :rank], rh[:, :rank], x, l2, dtype=dtype)
    upd_w = adagrad_np.position_updates(g["w"], rw[:, rank:], lr)
    upd_h = adagrad_np.position_updates(g["h"], rh[:, rank:], lr)
    np.add.at(table, wkeys, upd_w)
    np.add.at(table, hkeys, upd_h)
    return loss


def full_loss(rows, cols, vals, W, H, l2):
    """Sum over the revealed cells of (<W[i], H[j]> - x)^2, plus
    l2 (|W|^2 + |H|^2) over ALL of W and H: float32 products, float64
    sums."""
    pred = (W[rows] * H[cols]).sum(-1, dtype=np.float32)
    res = pred - np.asarray(vals, dtype=np.float32)
    err = (res * res).sum(dtype=np.float64)
    if not l2:
        return float(err)
    sq = (W * W).sum(dtype=np.float64) + (H * H).sum(dtype=np.float64)
    return float(err + l2 * sq)
