"""`serve_open_bags` against a TIERED store (`--sys.tier 1`): the whole
share of the tables in the host cold store, the configuration's
`cache_share` of every table in the device's hot pool, the tier's
maintenance worker live while requests arrive. The schedule, the client
threads, the timing, the reply check and the exact checks are
`serve_open_bags`'s own; this file adds what a tiered store needs of the
harness:

  the fill   the COLD store from the seed: `common.table_rows`' hash on
             the chip, a slab of slots at a time, each slab read back into
             the store's host array (numpy reproduces any row of it);
  the start  each table's most popular `ceil(cache_share x rows)` rows
             by the traffic's own rank, promoted through the program's
             own promotion path (`TierManager.promote_keys`);
  the checks rows the worker promoted or demoted INSIDE the window read
             back as their seeded rows; a push acknowledged to hot and to
             cold keys is read back exactly, and again after the hot
             ones were demoted and the cold ones promoted; residency's
             two maps are inverse to each other and the hot pool is
             within its bound at the window's end.
"""
from __future__ import annotations

import resource
import sys
import time
from fractions import Fraction

import numpy as np

from common import OUT, app_seed, rng_for, say, table_rows
from drivers import _ctr, serve_open_bags as bags
from reference import bags_np

PROMOTE_CHUNK = 1 << 19     # keys a call of the start residency's promotion


def fill_cold_from_seed(srv, keys: np.ndarray, emb_cols: int, scale: float,
                        seed: int, slab_f32: int = 1 << 25) -> np.ndarray:
    """Fill the one length class's host cold store with `table_rows` of
    the key that lives in each slot, computed on the device a slab of
    slots at a time (128 MiB of rows) and read back into the store's own
    array, the next slab's program running while this one crosses.
    Rounded to the pool's row type on the way, so a store built in a
    lower precision (`--control bf16`) holds that precision in both
    tiers. Returns the key of every slot (-1: none)."""
    import jax
    import jax.numpy as jnp
    store = srv.stores[0]
    cold = store.coldq.q            # [S, main_slots, L] float32
    S, M, L = cold.shape
    assert store.coldq.mode == "fp32"
    slot_key = np.full((S, M), -1, dtype=np.int32)
    slot_key[srv.ab.owner[keys], srv.ab.slot[keys]] = keys
    slab = min(max(1, slab_f32 // L), M)
    dtype = store.main.dtype

    @jax.jit
    def rows_of(ks):
        rows = table_rows(ks, L, emb_cols, scale, 0.0, seed, xp=jnp)
        rows = jnp.where((ks >= 0)[..., None], rows, 0)
        return rows.astype(dtype).astype(jnp.float32)

    # the last slab is moved back to end at M (slots written twice get
    # the same rows)
    starts = [min(lo, M - slab) for lo in range(0, M, slab)]
    ahead = None
    for i, lo in enumerate(starts):
        now = ahead if ahead is not None else \
            rows_of(slot_key[:, lo:lo + slab])
        ahead = rows_of(slot_key[:, starts[i + 1]:starts[i + 1] + slab]) \
            if i + 1 < len(starts) else None
        cold[:, lo:lo + slab] = np.asarray(now)
    return slot_key


def start_keys(serve, cfg: dict, zipfs) -> np.ndarray:
    """The start residency: of every table the `ceil(cache_share x
    rows)` ids the traffic names most (the first of the table's fixed
    popularity permutation), as feature keys."""
    share = Fraction(str(cfg["cache_share"]))
    out = []
    for t, (z, rows) in enumerate(zip(zipfs, cfg["table_rows"])):
        n = -(-share.numerator * rows // share.denominator)
        ranked = z.perm if z.perm is not None else np.arange(rows)
        out.append(serve.table_first[t] + ranked[:n])
    return np.concatenate(out).astype(np.int64)


def build_serve(ctx):
    """`CtrServe(args)` as `open_serve` builds it with `--sys.tier 1`,
    the cold store filled from the seed instead of `init_model()`'s
    host fill, the start residency promoted."""
    try:
        from adapm_tpu.apps import ctr
        from adapm_tpu.apps.ctr import CtrServe
        from adapm_tpu.tier.coldpath import precompile_gather_pool  # noqa
    except ImportError:
        # a checkout whose tier plane cannot carry the cell
        print("serve_open_bags_tier: this checkout has no tiered "
              "CtrServe the cell can run on", file=sys.stderr)
        raise SystemExit(2)
    cfg = ctx.cfg
    join = lambda xs: ",".join(str(x) for x in xs)  # noqa: E731
    smp = cfg["samples_per_request"]
    argv = ["--table_rows", join(cfg["table_rows"]),
            "--multi_hot_sizes", join(cfg["multi_hot_sizes"]),
            "--embedding_dim", str(cfg["embedding_dim"]),
            "--init_scale", str(cfg["init_scale"]),
            "--serve_samples", f"{smp['min']},{smp['max']}",
            "--num_shards", str(cfg["kv_shards"]),
            "--seed", str(app_seed(ctx.seed))] + list(cfg["app_args"])
    for name, value in cfg["sys"].items():
        argv += ["--sys." + name, str(value)]
    serve = CtrServe(ctr.build_parser().parse_args(argv))
    srv = serve.srv
    keys = np.arange(serve.n_feat, dtype=np.int64)
    t0 = time.perf_counter()
    slot_key = fill_cold_from_seed(srv, keys, cfg["embedding_dim"],
                                   cfg["init_scale"], ctx.seed)
    t1 = time.perf_counter()
    hot = start_keys(serve, cfg, _ctr._zipfs(cfg))
    for lo in range(0, len(hot), PROMOTE_CHUNK):
        srv.tier.promote_keys(hot[lo:lo + PROMOTE_CHUNK])
    srv.block()
    res = srv.stores[0].res
    say(f"CtrServe (tiered): {serve.n_feat} feature keys, rows of "
        f"{serve.dim} {srv.stores[0].main.dtype}; cold store "
        f"{srv.stores[0].coldq.q.shape} filled in {t1 - t0:.1f} s; hot "
        f"pool {srv.stores[0].main.shape}, {res.hot_count(0)} rows "
        f"promoted in {time.perf_counter() - t1:.1f} s")
    serve.slot_key = slot_key
    return serve


def setup(ctx) -> dict:
    # `serve_open_bags.setup` with this file's store under it: the same
    # table check, plane, sessions, schedule, kept requests and warm-up
    bags.build_serve = build_serve
    return bags.setup(ctx)


def _hot_mask(srv) -> np.ndarray:
    with srv._lock:
        return srv.stores[0].res.dev_row >= 0


def window(ctx, state) -> dict:
    srv = state["srv"]
    before = _hot_mask(srv)
    out = bags.window(ctx, state)
    moved = np.nonzero(before != _hot_mask(srv))
    out["moved_slots"] = moved
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    say(f"host memory the process has held at most (ru_maxrss): "
        f"{peak:.2f} GB")
    return out


def _tier_checks(ctx, state, out, checks) -> None:
    cfg, tr, srv = ctx.cfg, ctx.traffic, state["srv"]
    serve, lim = state["serve"], tr["tier_limits"]
    store = srv.stores[0]
    res = store.res
    dim = cfg["embedding_dim"]

    def seeded(ks):
        return bags_np.seeded_rows(ks, dim, cfg["init_scale"], ctx.seed)

    def main_rows(ks):
        return np.asarray(srv.read_main(ks)).reshape(len(ks), dim)

    # rows that changed tier inside the window, a sample of them
    sh, sl = out["moved_slots"]
    print(f"rows that changed tier inside the window: {len(sl)}",
          file=OUT, flush=True)
    checks.add("rows_moved_in_window", len(sl), 1, ok=len(sl) >= 1)
    rng = rng_for(ctx.seed, "tierchk")
    pick = rng.choice(len(sl), min(len(sl), tr["moved_keys_checked"]),
                      replace=False)
    ks = np.sort(serve.slot_key[sh[pick], sl[pick]].astype(np.int64))
    ks = ks[ks >= 0]
    checks.add("moved_rows_differ",
               int((main_rows(ks) != seeded(ks)).any(axis=1).sum())
               if len(ks) else 0, lim["moved_rows_differ"])
    # residency's maps at the window's end (the worker is live: under
    # the lock it moves rows under)
    with srv._lock:
        bad = over = 0
        for s in range(res.num_shards):
            slots = np.nonzero(res.dev_row[s] >= 0)[0]
            rows = np.nonzero(res.row_slot[s] >= 0)[0]
            bad += int(len(slots) != len(rows)) \
                + int(len(slots) != res.hot_count(s)) \
                + int((res.row_slot[s, res.dev_row[s, slots]]
                       != slots).sum()) \
                + int((res.dev_row[s, res.row_slot[s, rows]] != rows).sum())
            over += max(0, res.hot_count(s) - res.hot_rows)
    checks.add("residency_maps_disagree", bad,
               lim["residency_maps_disagree"])
    checks.add("hot_rows_over_capacity", over, lim["hot_rows_over_capacity"])
    # a push to hot and to cold keys, read back; then the hot ones
    # demoted (written rows: they have to be read back from the device)
    # and the cold ones promoted, and read back again
    n = tr["pushed_keys_checked"]
    all_keys = state["keys_all"]
    cand = rng.choice(all_keys, 64 * n, replace=False)
    with srv._lock:
        is_hot = res.dev_row[srv.ab.owner[cand], srv.ab.slot[cand]] >= 0
    hot, cold = cand[is_hot][:n], cand[~is_hot][:n]
    ks = np.sort(np.concatenate([hot, cold]))
    delta = rng.uniform(-1, 1, (len(ks), dim)).astype(np.float32)
    w0 = serve.workers[0]
    w0.wait(w0.push(ks, delta))
    srv.quiesce()
    want = seeded(ks) + delta
    checks.add("pushed_rows_not_read_back",
               int((main_rows(ks) != want).any(axis=1).sum())
               + 2 * n - len(ks), lim["pushed_rows_not_read_back"])
    srv.tier.demote_keys(hot)
    srv.tier.promote_keys(cold)
    checks.add("moved_pushed_rows_differ",
               int((main_rows(ks) != want).any(axis=1).sum()),
               lim["moved_pushed_rows_differ"])


def check(ctx, state, out, checks) -> None:
    """`serve_open_bags.check` (every pooled vector of the kept requests
    bitwise, the exact checks) after the tier's own."""
    _tier_checks(ctx, state, out, checks)
    bags.check(ctx, state, out, checks)


def close(ctx, state) -> None:
    bags.close(ctx, state)
