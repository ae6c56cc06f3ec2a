"""ComplEx with the sigmoid (negative-sampling logistic) loss, numpy float32.

An embedding of width 2d is a complex vector [re | im]. The score of a
triple is Re(<s, r, conj(o)>) (Trouillon et al. 2016); a batch of B triples,
each with N negative entities that corrupt the subject and the object side,
has the loss

    mean_b [ softplus(-score(s,r,o))
             + sum_j softplus(score(n_j, r, o)) + softplus(score(s, r, n_j)) ]

(upstream apps/knowledge_graph_embeddings.cc, sigmoid loss).
"""
from __future__ import annotations

import numpy as np


def _split(x):
    d = x.shape[-1] // 2
    return x[..., :d], x[..., d:]


def _d_a(r, c):
    """d score(a, r, c) / da, [..., 2d]. The score is linear in each of
    its three arguments, so this does not depend on a, and
    score(a, r, c) = (a * _d_a(r, c)).sum(-1)."""
    rr, ri = _split(r)
    cr, ci = _split(c)
    return np.concatenate([rr * cr + ri * ci, rr * ci - ri * cr], -1)


def _d_r(a, c):
    ar, ai = _split(a)
    cr, ci = _split(c)
    return np.concatenate([ar * cr + ai * ci, ar * ci - ai * cr], -1)


def _d_c(a, r):
    ar, ai = _split(a)
    rr, ri = _split(r)
    return np.concatenate([ar * rr - ai * ri, ai * rr + ar * ri], -1)


def score(a, r, c):
    ar, ai = _split(a)
    rr, ri = _split(r)
    cr, ci = _split(c)
    return (ar * rr * cr + ai * rr * ci + ar * ri * ci
            - ai * ri * cr).sum(-1, dtype=np.float32)


def _softplus(x):
    return np.logaddexp(np.float32(0), x).astype(np.float32)


def _sigmoid(x):
    return (np.float32(1) / (np.float32(1) + np.exp(-x))).astype(np.float32)


def lower(dtype):
    """x -> x rounded to `dtype` and back to float32 (the identity for
    float32): how the lower-precision control holds every value."""
    if dtype == np.float32:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(np.float32)


def loss_and_grads(s, r, o, neg, dtype=np.float32, batch_size=None):
    """s, r, o: [B, 2d]; neg: [B, N, 2d]. Returns (loss, dict of
    gradients per position: s, r, o [B, 2d], neg [B, N, 2d]).
    `dtype` is float32; the lower-precision control passes another, and
    inputs, scores, weights and gradients are then rounded to it.
    `batch_size`, where the arrays are a block of a larger batch, is that
    batch's size: the block's share of the batch's mean loss comes back,
    and the blocks' shares add up to it.

    The negatives are read four times and written once (they are 143k
    rows of 8 KB at the benchmark's size): their scores against the two
    derivative vectors of the positive triple, and their two weighted
    sums, from which the linear derivatives give the sums over j."""
    low = lower(dtype)
    s, r, o, neg = (low(x.astype(np.float32)) for x in (s, r, o, neg))
    B = np.float32(batch_size or s.shape[0])
    A, C = _d_a(r, o), _d_c(s, r)         # score(x,r,o) = x.A; score(s,r,x) = x.C
    pos = low((s * A).sum(-1, dtype=np.float32))
    ns = low(np.einsum("bnk,bk->bn", neg, A))    # subject corrupted
    no = low(np.einsum("bnk,bk->bn", neg, C))    # object corrupted
    loss = (_softplus(-pos) + _softplus(ns).sum(-1)
            + _softplus(no).sum(-1)).sum(dtype=np.float64) / float(B)
    w_pos = low(-_sigmoid(-pos) / B)[:, None]
    w_ns, w_no = low(_sigmoid(ns) / B), low(_sigmoid(no) / B)
    g_n = w_ns[..., None] * A[:, None, :]
    g_n += w_no[..., None] * C[:, None, :]
    sum_ns = np.einsum("bn,bnk->bk", w_ns, neg)  # sum_j w_ns[j] n_j
    sum_no = np.einsum("bn,bnk->bk", w_no, neg)
    g_s = w_pos * A + _d_a(r, sum_no)
    g_r = w_pos * _d_r(s, o) + _d_r(sum_ns, o) + _d_r(s, sum_no)
    g_o = w_pos * C + _d_c(sum_ns, r)
    grads = {"s": g_s, "r": g_r, "o": g_o, "neg": g_n}
    return float(loss), {k: low(v.astype(np.float32))
                         for k, v in grads.items()}
