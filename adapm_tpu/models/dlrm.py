"""DLRM with a low-rank DCNv2 interaction (Naumov et al. 2019; Wang et al.
2021), the click-through-rate model of MLPerf Training's recommendation
benchmark, as the loss of a fused step: the embedding tables AND the dense
network live in the parameter manager.

Per example: `p_f` = sum of the `h_f` member embeddings of feature f (its
bag; the multi-hot sizes `h_f` are fixed per feature); `d` = bottom MLP of
the dense features (ReLU after each layer); `x_0` = concat(d, p_0 ..
p_{F-1}); low-rank cross layers `x_{l+1} = x_0 * (W_l (V_l x_l) + b_l) +
x_l`; top MLP (ReLU between its layers, the last linear) gives the logit;
loss = mean binary cross-entropy.

Key layout: the tables' rows in table order, a row [embedding (dim) |
AdaGrad (dim)]; then the dense network's tensors in network order, each
flattened row-major and padded to whole rows [weights (row) | AdaGrad
(row)] of a second length class (`DenseLayout`).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dense_tensors(num_dense: int, emb_dim: int, num_features: int,
                  bottom: Sequence[int], top: Sequence[int],
                  dcn_layers: int, dcn_rank: int) -> List[Tuple]:
    """(name, shape, fan_in) of the dense network's tensors in network
    order: bottom MLP `num_dense -> bottom...` (weights [in, out]), the
    cross layers' V [rank, D0], W [D0, rank] and bias [D0] with D0 =
    (num_features + 1) * emb_dim, top MLP `D0 -> top...`."""
    assert bottom[-1] == emb_dim, "the bottom MLP ends at the embedding dim"
    d0 = (num_features + 1) * emb_dim
    def mlp(name, sizes):
        return [t for i, (a, b) in enumerate(zip(sizes, sizes[1:]))
                for t in ((f"{name}{i}.w", (a, b), a),
                          (f"{name}{i}.b", (b,), a))]

    out = mlp("bot", [num_dense, *bottom])
    for l in range(dcn_layers):
        out += [(f"cross{l}.v", (dcn_rank, d0), d0),
                (f"cross{l}.w", (d0, dcn_rank), dcn_rank),
                (f"cross{l}.b", (d0,), dcn_rank)]
    return out + mlp("top", [d0, *top])


class DenseLayout:
    """Where each dense tensor sits among the rows of the dense class:
    `rows[name]` = (first row, rows), every tensor padded to whole rows
    of `row` weights."""

    def __init__(self, tensors: List[Tuple], row: int):
        self.tensors, self.row = tensors, row
        self.rows: Dict[str, Tuple[int, int]] = {}
        at = 0
        for name, shape, _ in tensors:
            n = -(-int(np.prod(shape)) // row)
            self.rows[name] = (at, n)
            at += n
        self.num_rows = at
        self.num_params = sum(int(np.prod(s)) for _, s, _ in tensors)

    def unpack(self, rows) -> dict:
        """name -> tensor, sliced out of `rows` [num_rows, row] and
        reshaped (numpy or jax.numpy)."""
        out = {}
        for name, shape, _ in self.tensors:
            at, n = self.rows[name]
            flat = rows[at:at + n].reshape(-1)
            out[name] = flat[:int(np.prod(shape))].reshape(shape)
        return out

    def row_scale(self) -> np.ndarray:
        """Per row the bound of its tensor's uniform init, 1/sqrt(fan_in)
        (weights and biases alike, torch's Linear)."""
        scale = np.empty(self.num_rows, dtype=np.float32)
        for name, _, fan_in in self.tensors:
            at, n = self.rows[name]
            scale[at:at + n] = 1.0 / np.sqrt(fan_in)
        return scale


def bag_slices(multi_hot_sizes: Sequence[int]) -> List[Tuple[int, int]]:
    """(first member, one past the last) of each feature's bag among the
    members of an example."""
    ends = np.cumsum(multi_hot_sizes).tolist()
    return list(zip([0] + ends[:-1], ends))


def make_dlrm_loss(layout: DenseLayout, multi_hot_sizes: Sequence[int],
                   num_bottom: int, num_cross: int, num_top: int):
    """Roles: feat [M, B, dim], MEMBER-major (M = sum of the multi-hot
    sizes: a `[B, M, .]` array pads M to whole tiles and is copied), dense
    [layout.num_rows, layout.row]; aux = (dense features [B, num_dense],
    labels [B]). Matrix products at Precision.HIGHEST: the store is
    float32 and so is the step."""
    bags = bag_slices(multi_hot_sizes)

    def linear(x, t, name):
        return jnp.dot(x, t[name + ".w"], precision=HIGHEST) + t[name + ".b"]

    def loss_fn(embs, aux):
        x, y = aux
        feat = embs["feat"]
        with jax.named_scope("adapm_pool"):
            # a sum over static slices of axis 0: the sizes are fixed per
            # feature, so there are no ragged segments
            pooled = [feat[lo:hi].sum(0) for lo, hi in bags]
        with jax.named_scope("adapm_dense"):
            t = layout.unpack(embs["dense"])
            h = x.astype(feat.dtype)
            for i in range(num_bottom):
                h = jax.nn.relu(linear(h, t, f"bot{i}"))
            x0 = xl = jnp.concatenate([h] + pooled, axis=-1)
            for l in range(num_cross):
                v = jnp.dot(xl, t[f"cross{l}.v"].T, precision=HIGHEST)
                u = jnp.dot(v, t[f"cross{l}.w"].T, precision=HIGHEST) \
                    + t[f"cross{l}.b"]
                xl = x0 * u + xl
            h = xl
            for i in range(num_top):
                h = linear(h, t, f"top{i}")
                if i + 1 < num_top:
                    h = jax.nn.relu(h)
            z = h[:, 0]
            return (jax.nn.softplus(z) - y.astype(z.dtype) * z).mean()

    return loss_fn
