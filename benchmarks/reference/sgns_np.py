"""Skip-gram with negative sampling (Mikolov et al. 2013), numpy float32.

A batch of B (centre, context) pairs, each with N noise words; u is the
centre's input vector, v the context's output vector, n_j the noise words'
output vectors:

    mean_b [ softplus(-u.v) + sum_j softplus(u.n_j) ]
"""
from __future__ import annotations

import numpy as np

from .complex_np import _sigmoid, _softplus, lower


def loss_and_grads(center, ctx, neg, dtype=np.float32, batch_size=None):
    """center, ctx: [B, d]; neg: [B, N, d]. Returns (loss, gradients per
    position: center, ctx [B, d], neg [B, N, d]). `dtype` other than
    float32 is the lower-precision control: inputs, scores, weights and
    gradients are rounded to it. `batch_size`: as in `complex_np`."""
    low = lower(dtype)
    center, ctx, neg = (low(x.astype(np.float32))
                        for x in (center, ctx, neg))
    B = np.float32(batch_size or center.shape[0])
    pos = low((center * ctx).sum(-1, dtype=np.float32))
    ns = low(np.einsum("bnk,bk->bn", neg, center))
    loss = (_softplus(-pos)
            + _softplus(ns).sum(-1)).sum(dtype=np.float64) / float(B)
    w_pos = low(-_sigmoid(-pos) / B)[:, None]
    w_ns = low(_sigmoid(ns) / B)
    g_center = w_pos * ctx + np.einsum("bn,bnk->bk", w_ns, neg)
    g_ctx = w_pos * center
    g_neg = w_ns[..., None] * center[:, None, :]
    grads = {"center": g_center, "ctx": g_ctx, "neg": g_neg}
    return float(loss), {k: low(v.astype(np.float32))
                         for k, v in grads.items()}
