"""Embedding-bag serving support (ISSUE 16 tentpole a): the request
type, the batch planner, and the host pooling twin.

A *bag read* asks for POOLED vectors — per table, `bags` offsets
partition that table's member keys into segments and the reply is one
sum- or mean-pooled vector per segment (`ServeSession.lookup_bags`).
DLRM-style inference is dominated by exactly this access pattern
("Dissecting Embedding Bag Performance in DLRM Inference", PAPERS.md):
pooling on the host after a flat gather ships every member row over
the device boundary only to reduce it immediately, so the fused path
dispatches `ShardedStore.gather_pool` — gather + segment-reduce in ONE
device program per (length class, pooling) — and only the pooled
vectors cross.

Bit-identity contract: a pooled sum is the float32 sum of the bag's
member rows IN MEMBER ORDER, a repeated member summed as often as it is
named. `pool_bags_host` below accumulates with `np.add.at` in that
order; the fused program's `.at[seg].add` (`jaxport._pool_rows`) is
XLA's scatter-add, which on the CPU walks the batch in order and on a
TPU sorts (segment, position) pairs, ties by position, and adds each
bag's rows in that order: the v5e's replies were equal to the
member-order sum bit for bit in every run of the serving cell
(`dlrm-dcnv2-criteo1tb-serve.bags-open`: bags of 1 to 100 members,
every pooled vector of 65 kept requests a run, half a million, compared
with `benchmarks/reference/bags_np.py`; PERF.md section 6, PR 37). That
is what lets the batcher pick per dispatch
(replica snapshot → host pool; locked path → fused device pool;
multi-process or `--sys.serve.bags 0` → flat union gather + host pool)
without the choice being observable in the returned bits
(scripts/portdiff_check.py pins this across ports on the CPU;
`tests/test_bags_reference.py` holds the three paths to the reference).
The order is a property of the compiled scatter, not of its contract:
the benchmark's cell checks it on the chip in every run.

Before traffic: a coalesced batch's members and bags are padded to
powers of two and each pair of buckets is one compiled program, so a
deployment calls `ServePlane.precompile_bags` with the sizes its batches
can have (`apps/ctr.py CtrServe.open_plane`); `--sys.serve.max_batch`
bounds a batch in requests, and with it the fullest program's gathered
rows.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from .admission import LookupRequest


class BagLookupRequest(LookupRequest):
    """One client bag lookup riding the same admission queue / claim
    machinery as a flat `LookupRequest`. `keys` is the flat concat of
    every table's member keys (what admission, lane assignment, and
    union dedup see); `tables`/`bags` keep the per-table structure the
    pooling needs. Delivery carries the flat concat of the per-table
    pooled matrices (`[nbags_t, L_t]` row-major, tables in order) —
    the session reshapes."""

    __slots__ = ("tables", "bags", "pooling")

    def __init__(self, tables: Sequence[np.ndarray],
                 bags: Sequence[np.ndarray], pooling: str,
                 keys: np.ndarray, **kw):
        super().__init__(keys, **kw)
        self.tables = list(tables)
        self.bags = list(bags)
        self.pooling = pooling


def pool_bags_host(rows: np.ndarray, seg: np.ndarray, nbags: int,
                   pooling: str) -> np.ndarray:
    """Pool member `rows` [n, L] into [nbags, L] on the host — the
    bit-identical twin of the device program (module docstring):
    batch-order `np.add.at` sum, then for mean ONE division per bag
    (empty bags pool to exact zeros, matching the device masked
    divide)."""
    rows = np.asarray(rows)
    seg = np.asarray(seg)
    out = np.zeros((int(nbags), rows.shape[1]), dtype=rows.dtype)
    np.add.at(out, seg, rows)
    if pooling == "sum":
        return out
    cnt = np.zeros(int(nbags), dtype=rows.dtype)
    np.add.at(cnt, seg, rows.dtype.type(1))
    denom = np.where(cnt > 0, cnt, rows.dtype.type(1))[:, None]
    return np.where(cnt[:, None] > 0, out / denom, np.zeros_like(out))


# a group key is (length-class id, pooling) — one device program (or
# one host pool) per group serves every request's tables in that group
GroupKey = Tuple[int, str]


def plan_bag_batch(reqs: List[BagLookupRequest], key_class: np.ndarray):
    """Coalesce a batch of bag requests into per-(class, pooling)
    groups. Returns `(groups, slices)`:

      groups[gkey] = {"keys": member keys (concat, REQUEST ORDER —
                      the order the pooling accumulates in), "seg":
                      int32 global bag index per member, "nbags": int}
      slices[i]    = [(gkey, bag_start, nbags_t), ...] per request i's
                     tables, in table order — slice the group's pooled
                     matrix `[bag_start : bag_start + nbags_t]` to get
                     that table's reply.

    Member DUPLICATES are preserved (each member position is one
    accumulation entry — dedup here would change the pooled sums);
    union dedup for replica-coverage/metrics happens on the caller's
    side over `req.keys`."""
    groups: Dict[GroupKey, dict] = {}
    slices: List[list] = []
    for r in reqs:
        rs = []
        for ks, bg in zip(r.tables, r.bags):
            gkey = (int(key_class[ks[0]]), r.pooling)
            g = groups.setdefault(gkey,
                                  {"keys": [], "seg": [], "nbags": 0})
            nb = len(bg) - 1
            seg = (np.repeat(np.arange(nb, dtype=np.int64),
                             np.diff(bg)).astype(np.int32) + g["nbags"])
            g["keys"].append(ks)
            g["seg"].append(seg)
            rs.append((gkey, g["nbags"], nb))
            g["nbags"] += nb
        slices.append(rs)
    for g in groups.values():
        g["keys"] = np.concatenate(g["keys"])
        g["seg"] = np.concatenate(g["seg"]).astype(np.int32) \
            if g["seg"] else np.empty(0, np.int32)
    return groups, slices
