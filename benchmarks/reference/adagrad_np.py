"""The store's row update, as the parameter manager defines it.

A row is [embedding | AdaGrad accumulator]. Every POSITION of a batch that
names the row pushes, additively and from the row's value before the step,

    d_emb = -lr * g / sqrt(acc + g*g + eps)        d_acc = g*g

(upstream AdaPM: the update is computed at the worker from the pulled value
and pushed; pushes add up at the main copy). Positions that name the same
row therefore add up; they do not see each other.
"""
from __future__ import annotations

import numpy as np

EPS = np.float32(1e-10)


def position_updates(g: np.ndarray, acc: np.ndarray, lr: float) -> np.ndarray:
    """[..., 2w] update (d_emb | d_acc) for gradients g and pre-step
    accumulators acc, both [..., w] float32."""
    g = g.astype(np.float32, copy=False)
    w = g.shape[-1]
    upd = np.empty(g.shape[:-1] + (2 * w,), dtype=np.float32)
    d_emb, g2 = upd[..., :w], upd[..., w:]
    np.multiply(g, g, out=g2)
    root = acc + g2
    root += EPS
    np.sqrt(root, out=root)
    np.multiply(g, -np.float32(lr), out=d_emb)
    d_emb /= root
    return upd


class RowState:
    """The rows a few steps touch, held by key: starts from rows the
    caller generates, evolves by `add`."""

    def __init__(self, row_len: int):
        self.keys = np.empty(0, dtype=np.int64)
        self.rows = np.empty((0, row_len), dtype=np.float32)

    def ensure(self, keys: np.ndarray, make_rows) -> None:
        """Add the rows of keys not yet held; `make_rows(keys)` gives
        their initial values."""
        new = np.setdiff1d(np.unique(keys), self.keys)
        if not len(self.keys):
            self.keys, self.rows = new, make_rows(new)
        elif len(new):
            allk = np.concatenate([self.keys, new])
            order = np.argsort(allk, kind="stable")
            self.rows = np.concatenate([self.rows, make_rows(new)])[order]
            self.keys = allk[order]

    def index(self, keys: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.keys, keys)

    def get(self, keys: np.ndarray) -> np.ndarray:
        return self.rows[self.index(keys)]

    def add(self, keys: np.ndarray, upd: np.ndarray, into=None) -> None:
        """Add upd[i] to the row of keys[i] (of `into`, an array shaped
        like the rows, where the caller gathers a step's pushes before
        they land); duplicates add up, in the order given. Round k adds
        the k-th occurrence of every row, so a round names no row twice
        (`np.add.at` over whole rows is minutes at 2,000 columns)."""
        rows = self.rows if into is None else into
        idx = self.index(keys.ravel())
        upd = upd.reshape(-1, upd.shape[-1])
        order = np.argsort(idx, kind="stable")
        sorted_idx = idx[order]
        first = np.r_[True, sorted_idx[1:] != sorted_idx[:-1]]
        start = np.maximum.accumulate(np.where(first, np.arange(len(idx)), 0))
        rank = np.arange(len(idx)) - start       # occurrence number
        if not rank.any():
            rows[idx] += upd
            return
        for k in range(int(rank.max()) + 1):
            sel = order[rank == k]
            rows[idx[sel]] += upd[sel]
