"""Skip-gram negative-sampling word2vec (SGNS).

Reference apps/word2vec.cc (Google-C w2v ported to the PM): two keys per
word — syn0 (input embedding) = 2w, syn1 (output embedding) = 2w+1
(word2vec.cc:83-105); unigram^0.75 negative table (:125-144); AdaGrad
update (:718-743). Here one fused step trains a whole batch of (center,
context) pairs with N shared-per-pair negatives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def syn0_key(word: np.ndarray):
    """Input-embedding key for word id(s) (word2vec.cc:83-105)."""
    return 2 * np.asarray(word, dtype=np.int64)


def syn1_key(word: np.ndarray):
    """Output-embedding key for word id(s)."""
    return 2 * np.asarray(word, dtype=np.int64) + 1


def sgns_loss(embs, aux):
    """Roles: center [B, d] (syn0), ctx [B, d] (syn1), neg [B, N, d] (syn1).
    loss = -log sig(u.v) - sum log sig(-u.v_neg)."""
    center, ctx, neg = embs["center"], embs["ctx"], embs["neg"]
    pos = (center * ctx).sum(-1)
    negs = (center[:, None, :] * neg).sum(-1)
    return (jax.nn.softplus(-pos) + jax.nn.softplus(negs).sum(-1)).mean()


def build_alias_table(counts: np.ndarray, power: float = 0.75):
    """Vose alias table for the unigram^power noise distribution — the
    device-sampler form of the reference's pre-materialized 1e8-entry
    unigram table (word2vec.cc:125-144): two O(V) arrays in HBM instead of
    a 400MB table, sampled in-program with two uniform draws.
    Returns (prob float32[V], alias int32[V])."""
    p = counts.astype(np.float64) ** power
    p /= p.sum()
    V = len(p)
    prob = np.zeros(V, dtype=np.float32)
    alias = np.zeros(V, dtype=np.int32)
    scaled = p * V
    small = [i for i in range(V) if scaled[i] < 1.0]
    large = [i for i in range(V) if scaled[i] >= 1.0]
    while small and large:
        s, l = small.pop(), large.pop()
        prob[s] = scaled[s]
        alias[s] = l
        scaled[l] = (scaled[l] + scaled[s]) - 1.0
        (small if scaled[l] < 1.0 else large).append(l)
    for i in small + large:
        prob[i] = 1.0
    return prob, alias


def subsample_mask(word_counts: np.ndarray, words: np.ndarray,
                   total: int, t: float, rng) -> np.ndarray:
    """Frequent-word subsampling keep-mask, word2vec.c's keep probability
    sqrt(t/f) + t/f for a word with corpus frequency f (word2vec.cc applies
    this while filling its sentence buffer)."""
    f = word_counts[words] / max(total, 1)
    keep_p = np.minimum(1.0, np.sqrt(t / np.maximum(f, 1e-12))
                        + t / np.maximum(f, 1e-12))
    return rng.random(len(words)) < keep_p
