"""DLRM with a low-rank DCNv2 interaction and the binary cross-entropy,
numpy float32 with a hand-written backward (MLPerf Training DLRM-DCNv2;
torchrec `dlrm_main.py --interaction_type dcn`).

A batch of B examples. Example b has M member embeddings e[m, b] of width
d, the members of F bags of fixed sizes h_f (sum h_f = M, bags in table
order), dense features x[b] and a label y[b]:

    p_f   = sum of the h_f members of bag f
    d     = bottom MLP of x: ReLU(. W + b) layer by layer, ends at width d
    x_0   = concat(d, p_0 .. p_{F-1})                  width D0 = (F+1) d
    x_l+1 = x_0 * (W_l (V_l x_l) + b_l) + x_l          V_l [rank, D0],
                                                       W_l [D0, rank]
    z     = top MLP of x_L: ReLU between its layers, the last linear
    loss  = mean_b [ softplus(z_b) - y_b z_b ]

The dense network's tensors live in the store as rows of `row` weights:
each tensor flattened row-major and padded to whole rows, the tensors in
network order (`tensors`, `unpack`, `pack`). A training step pushes, for
every POSITION and from the rows as they were before the step, the AdaGrad
update of `adagrad_np`: the M x B member positions in the order of the
flattened [M, B] keys, then the dense rows.
"""
from __future__ import annotations

import numpy as np

from . import adagrad_np
from .complex_np import _sigmoid, _softplus, lower


def tensors(num_dense, emb_dim, num_features, bottom, top, dcn_layers,
            dcn_rank):
    """[(name, shape, fan_in)] of the dense network in network order;
    MLP weights are [in, out]."""
    d0 = (num_features + 1) * emb_dim
    out = []
    sizes = [num_dense] + list(bottom)
    for i in range(len(bottom)):
        out.append((f"bot{i}.w", (sizes[i], sizes[i + 1]), sizes[i]))
        out.append((f"bot{i}.b", (sizes[i + 1],), sizes[i]))
    for l in range(dcn_layers):
        out.append((f"cross{l}.v", (dcn_rank, d0), d0))
        out.append((f"cross{l}.w", (d0, dcn_rank), dcn_rank))
        out.append((f"cross{l}.b", (d0,), dcn_rank))
    sizes = [d0] + list(top)
    for i in range(len(top)):
        out.append((f"top{i}.w", (sizes[i], sizes[i + 1]), sizes[i]))
        out.append((f"top{i}.b", (sizes[i + 1],), sizes[i]))
    return out


def rows_of(tens, row: int):
    """name -> (first row, rows) of each tensor, and the rows in all."""
    where, at = {}, 0
    for name, shape, _ in tens:
        n = -(-int(np.prod(shape)) // row)
        where[name] = (at, n)
        at += n
    return where, at


def unpack(rows: np.ndarray, tens, row: int) -> dict:
    """The tensors out of the dense rows' weight columns [n_rows, row]."""
    where, _ = rows_of(tens, row)
    out = {}
    for name, shape, _ in tens:
        at, n = where[name]
        out[name] = rows[at:at + n].reshape(-1)[:int(np.prod(shape))] \
            .reshape(shape)
    return out


def pack(grads: dict, tens, row: int) -> np.ndarray:
    """Gradients by tensor back into rows [n_rows, row]; the padding of a
    tensor's last row gets no gradient."""
    where, total = rows_of(tens, row)
    out = np.zeros((total, row), dtype=np.float32)
    for name, shape, _ in tens:
        at, n = where[name]
        flat = out[at:at + n].reshape(-1)
        flat[:int(np.prod(shape))] = grads[name].reshape(-1)
    return out


def loss_and_grads(feat, dense, x, y, multi_hot_sizes, n_bottom, n_cross,
                   n_top, dtype=np.float32):
    """feat [M, B, d] member embeddings; dense: name -> tensor; x [B,
    num_dense]; y [B]. Returns (loss, gradient of feat [M, B, d] per
    position, gradients of the dense tensors by name). `dtype` other than
    float32 is the lower-precision control: inputs, every layer's output
    and every gradient are rounded to it (products accumulate in
    float32, as a matrix unit does)."""
    low = lower(dtype)
    feat = low(feat.astype(np.float32))
    t = {k: low(v.astype(np.float32)) for k, v in dense.items()}
    x = low(np.asarray(x, dtype=np.float32))
    y = np.asarray(y, dtype=np.float32)
    B = np.float32(feat.shape[1])
    d = feat.shape[2]
    ends = np.cumsum(multi_hot_sizes).tolist()
    bags = list(zip([0] + ends[:-1], ends))

    pooled = [low(feat[lo:hi].sum(0, dtype=np.float32)) for lo, hi in bags]
    h, bot = x, []
    for i in range(n_bottom):
        a = low(h @ t[f"bot{i}.w"] + t[f"bot{i}.b"])
        bot.append((h, a))
        h = np.maximum(a, np.float32(0))
    x0 = xl = np.concatenate([h] + pooled, axis=-1)
    cross = []
    for l in range(n_cross):
        v = low(xl @ t[f"cross{l}.v"].T)
        u = low(v @ t[f"cross{l}.w"].T + t[f"cross{l}.b"])
        cross.append((xl, v, u))
        xl = low(x0 * u + xl)
    h, top = xl, []
    for i in range(n_top):
        a = low(h @ t[f"top{i}.w"] + t[f"top{i}.b"])
        top.append((h, a))
        h = np.maximum(a, np.float32(0)) if i + 1 < n_top else a
    z = h[:, 0]
    loss = (_softplus(z) - y * z).sum(dtype=np.float64) / float(B)

    g = {}
    dh = low((_sigmoid(z) - y) / B)[:, None]
    for i in reversed(range(n_top)):
        h_in, a = top[i]
        da = dh if i + 1 == n_top else dh * (a > 0)
        g[f"top{i}.w"] = h_in.T @ da
        g[f"top{i}.b"] = da.sum(0, dtype=np.float32)
        dh = low(da @ t[f"top{i}.w"].T)
    dxl, dx0 = dh, np.zeros_like(x0)
    for l in reversed(range(n_cross)):
        xl_in, v, u = cross[l]
        du = low(dxl * x0)
        dx0 += dxl * u
        g[f"cross{l}.b"] = du.sum(0, dtype=np.float32)
        g[f"cross{l}.w"] = du.T @ v
        dv = low(du @ t[f"cross{l}.w"])
        g[f"cross{l}.v"] = dv.T @ xl_in
        dxl = low(dxl + dv @ t[f"cross{l}.v"])
    dx0 = low(dx0 + dxl)
    g_feat = np.empty_like(feat)
    for f, (lo, hi) in enumerate(bags):
        g_feat[lo:hi] = dx0[:, (f + 1) * d:(f + 2) * d]
    dh = dx0[:, :d]
    for i in reversed(range(n_bottom)):
        h_in, a = bot[i]
        da = dh * (a > 0)
        g[f"bot{i}.w"] = h_in.T @ da
        g[f"bot{i}.b"] = da.sum(0, dtype=np.float32)
        dh = low(da @ t[f"bot{i}.w"].T)
    return float(loss), g_feat, {k: low(v.astype(np.float32))
                                 for k, v in g.items()}


def position_updates(g, acc, lr, eps):
    """`adagrad_np.position_updates` under the configuration's damping
    `eps` in place of that module's constant: it is that much more in
    every accumulator before the step (acc + g*g + eps); an
    accumulator's own change is g*g either way."""
    return adagrad_np.position_updates(
        g, acc + (np.float32(eps) - adagrad_np.EPS), lr)


def step(feat_table, dense_table, feat_keys, x, y, tens, row,
         multi_hot_sizes, n_bottom, n_cross, n_top, lr, dtype=np.float32,
         eps=adagrad_np.EPS):
    """One training step on `feat_table` [keys, 2d] = [embedding | AdaGrad]
    and `dense_table` [rows, 2 row] = [weights | AdaGrad], in place;
    returns the batch's loss. `feat_keys` [M, B] index `feat_table`; every
    dense row is named once. Every position's update is formed from the
    rows as they stand before the step, and `np.add.at` adds them to
    their rows in the order of the flattened [M, B] keys: positions that
    name one row add up and do not see each other."""
    d = feat_table.shape[1] // 2
    rows = feat_table[feat_keys]
    loss, g_feat, g = loss_and_grads(
        rows[..., :d], unpack(dense_table[:, :row], tens, row), x, y,
        multi_hot_sizes, n_bottom, n_cross, n_top, dtype=dtype)
    upd_f = position_updates(g_feat, rows[..., d:], lr, eps)
    upd_d = position_updates(pack(g, tens, row), dense_table[:, row:], lr,
                             eps)
    np.add.at(feat_table, feat_keys.reshape(-1), upd_f.reshape(-1, 2 * d))
    dense_table += upd_d
    return loss
