"""Pooled bag reads over a seeded embedding table, numpy float32: what a
ranking model's embedding shard answers a query with.

A table holds one row of `dim` floats a key. A request names, for each
of T tables, member keys and the offsets that cut them into bags (the
`nn.EmbeddingBag` convention: bag b of a table is members
offsets[b]:offsets[b + 1]); the reply is, per table, one vector a bag:

    pooled[b] = row(k_0) + row(k_1) + ... + row(k_{m-1})

summed in float32 IN MEMBER ORDER, left to right from 0, a repeated
member summed as often as it is named, an empty bag all zeros. Float32
addition is not associative: another order gives other bits, within
`order_bound`.

The rows are the seeded table's: `seeded_rows` is this file's own copy
of the hash `benchmarks/common.py table_rows` fills the device table
with (`selfcheck.py` holds the two together). Imports nothing of the
program.
"""
from __future__ import annotations

import numpy as np

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35


def _mix(x):
    """murmur3's 32-bit finalizer over a uint32 array."""
    u = np.uint32
    x = x * u(_M1)
    x = x ^ (x >> u(16))
    x = x * u(_M2)
    x = x ^ (x >> u(13))
    x = x * u(_M3)
    return x ^ (x >> u(16))


_BLOCK = 2048    # keys hashed at a time: the temporaries stay in the cache


def seeded_rows(keys, dim: int, scale: float, seed: int) -> np.ndarray:
    """[n, dim] rows of `keys`: uniform in [-scale, scale) from a hash of
    (seed, key, column)."""
    seed = int(seed)
    s32 = np.uint32((seed ^ (seed >> 32) ^ 0x5BD1E995) & 0xFFFFFFFF)
    keys = np.asarray(keys).reshape(-1)
    col = np.arange(dim, dtype=np.uint32)
    out = np.empty((keys.size, dim), dtype=np.float32)
    for lo in range(0, keys.size, _BLOCK):
        k = keys[lo:lo + _BLOCK].astype(np.uint32)[:, None]
        h = _mix(_mix(k ^ s32) + col)
        unit = (h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)
        out[lo:lo + _BLOCK] = (unit - np.float32(0.5)) \
            * np.float32(2.0 * scale)
    return out


def pool(rows: np.ndarray, offsets: np.ndarray):
    """(sums [nbags, dim], sums of |row| [nbags, dim]) of member `rows`
    [n, dim] cut into bags by `offsets` [nbags + 1]. Each bag is summed
    in member order: the j-th members of all bags that have one are
    added in the j-th pass, so a bag's own additions run 0, 1, 2, ...
    whatever the other bags hold. The second result is what
    `order_bound` needs."""
    rows = np.asarray(rows, dtype=np.float32)
    offsets = np.asarray(offsets, dtype=np.int64)
    size = np.diff(offsets)
    out = np.zeros((len(size), rows.shape[1]), dtype=np.float32)
    mag = np.zeros_like(out)
    for j in range(int(size.max(initial=0))):
        has = np.flatnonzero(size > j)
        r = rows[offsets[has] + j]
        out[has] += r
        mag[has] += np.abs(r)
    return out, mag


def order_bound(mag: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """The room a float32 sum of a bag's rows taken in another order is
    given around the member-order sum, per element: (m - 1) x 2^-24 x
    sum|row_i| (the rounding of m - 1 additions, each at most half an
    ulp of a partial sum no larger than sum|row_i|), 0 for a bag of one
    member: that one is exact in any order."""
    m = np.diff(np.asarray(offsets, dtype=np.int64)).astype(np.float32)
    return (np.maximum(m - 1, 0) * np.float32(2.0 ** -24))[:, None] * mag


def reply(tables, bags, dim: int, scale: float, seed: int):
    """A request's reply from the seeded table: per table (pooled sums,
    sums of |row|). `tables[t]`: member keys, `bags[t]`: offsets."""
    return [pool(seeded_rows(ks, dim, scale, seed), bg)
            for ks, bg in zip(tables, bags)]
