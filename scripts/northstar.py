"""North-star scale runs on one TPU chip:

  kge  — Wikidata5M-sized ComplEx: 4.6M entities / 822 relations, d=128,
         B=4096, 32 negatives; reports ms/step and the derived epoch time
         over Wikidata5M's 20.6M train triples.
  w2v  — 1B-words-sized SGNS: 800k vocab (the benchmark corpus' min-count-5
         vocabulary), d=128, B=8192 pairs, 5 negatives with on-device
         unigram^0.75 alias sampling; reports pairs/s.
  mf   — MovieLens-25M-sized: 162,541 users x 59,047 movies, rank 128,
         B=16384 ratings; reports updates/s and derived epoch time over
         25M ratings.

Each run drives the apps' PM loop (intent for the next batch + a
planner round per step, the fused step) at full table scale —
the point is the table SIZE (the KGE table fills most of a v5e chip's
HBM; `--sys.main_over_alloc` close to 1 trades relocation headroom for
fitting), not new machinery. Timing is slope-based (`slope_time`); its
numbers are read by nothing (PERF.md, head). Prints one JSON line per
workload.

Usage: python scripts/northstar.py [kge w2v mf]
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

import os

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def progress(msg: str) -> None:
    print(f"[northstar +{time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.perf_counter()


def bulk_device_init(store, emb_cols: int, scale: float, seed: int) -> None:
    """Fill a store's whole main table: normal(0, scale) embedding
    columns, 1e-6 optimizer-state columns. Slot assignment is irrelevant —
    every slot gets an i.i.d. row, so this equals a per-key host init in
    distribution.

    Untiered: one device fill program (skips the host->HBM transfer
    entirely — a 4.6M x 512 table inits in milliseconds instead of
    minutes), constructed through the DevicePort like every other
    program (ISSUE 14). Tiered (--tier): the authoritative table IS the
    host cold store, so the init is a host fill — rows promote lazily
    to the HBM hot pool as the workload touches them, which is the
    point: the table no longer needs to fit on the chip."""
    import jax
    import jax.numpy as jnp

    from adapm_tpu.device import default_port

    if store.res is not None:
        # tiered: fill the cold store host-side (slabbed generation; at
        # full KGE scale this is the one place the host pays the table)
        from adapm_tpu.tier.coldpath import install_main_full
        S, M, L = store.main_shape_full
        rng = np.random.default_rng(seed)
        full = rng.standard_normal((S, M, L), dtype=np.float32)
        full *= np.float32(scale)  # in place: a second full-size array
        # here would transiently DOUBLE host RSS at KGE scale
        full[:, :, emb_cols:] = 1e-6
        install_main_full(store, full)
        return

    S, M, L = store.main.shape
    slab = min(M, 262_144)

    def fill(main, key, lo):
        r = jax.random.normal(key, (S, slab, L), main.dtype) * scale
        r = r.at[:, :, emb_cols:].set(1e-6)
        return jax.lax.dynamic_update_slice(main, r, (0, lo, 0))

    fill = default_port().compile(fill, donate_argnums=0)
    key = jax.random.PRNGKey(seed)
    lo = 0
    while lo < M:
        key, sub = jax.random.split(key)
        # dynamic_update_slice clamps the final slab to [M-slab, M)
        store.main = fill(store.main, sub, jnp.int32(min(lo, M - slab)))
        lo += slab
    store.block()


# --tier (ISSUE 14 satellite): run the scale workloads on the TIERED
# store. The KGE table then no longer needs --sys.main_over_alloc≈1 to
# fit a chip: the authoritative table lives in the host cold store and
# only TIER_HOT_FRAC of the keys (per shard) occupy HBM, promoted by
# the intent windows the pm loop already declares — and every program
# rides the DevicePort like the rest of the tree.
TIER = False
TIER_HOT_FRAC = 0.25


def _sys_opts(num_keys: int, **kw):
    from adapm_tpu.config import SystemOptions
    if TIER:
        import jax
        S = len(jax.devices())
        # no HBM squeeze under tier: main_slots beyond the hot pool are
        # host rows, so the relocation-headroom default costs no HBM
        kw.pop("main_over_alloc", None)
        kw.update(tier=True,
                  tier_hot_rows=max(8, -(-int(num_keys * TIER_HOT_FRAC)
                                         // S)))
    return SystemOptions(cache_slots_per_shard=1, sync_max_per_sec=0,
                         **kw)


def skewed(rng, n, size):
    return (n * rng.random(size) ** 3).astype(np.int64).clip(0, n - 1)


def slope_time(step, steps: int):
    """(T_long - T_short) / (steps - steps//4); step(i) must end in a
    host-visible value only when asked."""
    assert steps >= 4, "slope timing needs steps >= 4 (two loop lengths)"

    def timed(n):
        t0 = time.perf_counter()
        out = None
        for i in range(n):
            out = step(i)
        float(out)
        return time.perf_counter() - t0

    timed(1)
    t_s = timed(steps // 4)
    t_l = timed(steps)
    return (t_l - t_s) / (steps - steps // 4)


def pm_loop(srv, w, runner, batches, aux, lr, steps, warmup):
    """The apps' PM step shape: intent for the NEXT batch, fused step,
    one planner round, clock tick."""
    nb = len(batches)
    intent_keys = [np.unique(np.concatenate([v.ravel() for v in b.values()]))
                   for b in batches]

    def step(i):
        nxt = (i + 1) % nb
        w.intent(intent_keys[nxt], w.current_clock + 1, w.current_clock + 2)
        loss = runner(batches[i % nb], None if aux is None else aux[i % nb],
                      lr)
        srv.sync.run_round()
        w.advance_clock()
        return loss

    for _ in range(warmup):
        step(0)
    return slope_time(step, steps)


def run_kge(E=4_600_000, R=822, d=128, B=4096, N=32, steps=16,
            train_triples=20_614_279, full_epoch=False, do_eval=False):
    import adapm_tpu
    from adapm_tpu.models import make_kge_loss
    from adapm_tpu.ops import DeviceRoutedRunner

    progress(f"kge: building server ({E + R} keys x {4 * d} f32 = "
             f"{(E + R) * 4 * d * 4 / 2**30:.1f} GiB main table"
             + (", tiered)" if TIER else " on device)"))
    srv = adapm_tpu.setup(E + R, 4 * d,
                          opts=_sys_opts(E + R, main_over_alloc=1.02))
    bulk_device_init(srv.stores[0], 2 * d, 0.1, seed=0)
    progress("kge: init done (device bulk init)")
    w = srv.make_worker(0)
    runner = DeviceRoutedRunner(
        srv, make_kge_loss("complex"),
        role_class={"s": 0, "r": 0, "o": 0, "neg": 0},
        role_dim={k: 2 * d for k in ("s", "r", "o", "neg")},
        neg_role="neg", neg_shape=(B, N), neg_population=np.arange(E))
    rng = np.random.default_rng(0)
    batches = [{"s": skewed(rng, E, B),
                "r": rng.integers(E, E + R, B).astype(np.int64),
                "o": skewed(rng, E, B)} for _ in range(4)]
    progress("kge: compiling + warmup")
    dt = pm_loop(srv, w, runner, batches, None, 0.1, steps, warmup=3)
    out = {"metric": "northstar_kge_wikidata5m_scale",
           "entities": E, "relations": R, "dim": d,
           "ms_per_step": round(dt * 1e3, 2),
           "triples_per_sec": round(B / dt, 1),
           "derived_epoch_s_20.6M_triples": round(dt * train_triples / B,
                                                  1)}
    if full_epoch:
        # measure one ACTUAL epoch end-to-end (every step ships a fresh
        # host batch + intent + planner round), not the slope-derived
        # steady state
        n_steps = -(-train_triples // B)
        progress(f"kge: full epoch ({n_steps} steps)")

        def fresh():
            return {"s": skewed(rng, E, B),
                    "r": rng.integers(E, E + R, B).astype(np.int64),
                    "o": skewed(rng, E, B)}

        t0 = time.perf_counter()
        loss = None
        nxt = fresh()
        for i in range(n_steps):
            b, nxt = nxt, fresh()
            # the pm_loop step shape: intent covers the NEXT batch one
            # clock ahead, then the current batch trains
            w.intent(np.unique(np.concatenate(
                [nxt["s"], nxt["r"], nxt["o"]])), w.current_clock + 1,
                w.current_clock + 2)
            loss = runner(b, None, 0.1)
            srv.sync.run_round()
            w.advance_clock()
        float(loss)
        out["measured_epoch_s"] = round(time.perf_counter() - t0, 1)
        progress(f"kge: epoch done in {out['measured_epoch_s']} s")
    if do_eval:
        # full-entity chunked eval at table scale (VERDICT r3 item 4):
        # candidates gathered from the pool in [B_ev, C] tiles, only [B_ev]
        # rank counts return to the host (models/kge.make_pool_eval_counts)
        from adapm_tpu.models.kge import make_pool_eval_counts
        from adapm_tpu.ops import DeviceRouter
        C = 65_536
        put = srv.ctx.put_replicated
        nch = -(-E // C)
        pad = np.zeros(nch * C, dtype=np.int64)
        pad[:E] = np.arange(E)
        ent_keys_dev = put(pad.reshape(nch, C))
        tables = DeviceRouter(srv, 0).tables()
        ent_main = srv.stores[0].main
        # shared_pool: entities and relations live in ONE length class at
        # this scale; passing the 8.8 GiB pool as two parameters doubles
        # the AOT argument budget and the compile is rejected (OOM)
        fn = make_pool_eval_counts("complex", 2 * d, 2 * d, C,
                                   shared_pool=True)
        # two batch sizes: 64 = the app default; 512 amortizes the same
        # candidate gathers over 8x the triples (the count program is
        # gather-dominated at B=64 — the [B, d] x [d, C] matmuls are too
        # skinny to feed the MXU)
        for B_ev in (64, 512):
            ev_batches = [
                (put(skewed(rng, E, B_ev)),
                 put(rng.integers(E, E + R, B_ev).astype(np.int64)),
                 put(skewed(rng, E, B_ev))) for _ in range(4)]
            progress(f"kge: eval compile + timing (B={B_ev})")

            def ev_step(i):
                s, r, o = ev_batches[i % 4]
                g_o, g_s, _ = fn(ent_main, tables, ent_keys_dev,
                                 np.int32(E), s, r, o)
                return g_o.sum() + g_s.sum()

            dt_ev = slope_time(ev_step, 12)
            out[f"eval_ms_per_batch{B_ev}"] = round(dt_ev * 1e3, 2)
            out[f"eval_triples_per_sec_b{B_ev}"] = round(B_ev / dt_ev, 1)
            out[f"derived_eval_s_per_10k_triples_b{B_ev}"] = \
                round(dt_ev / B_ev * 1e4, 1)
            progress(f"kge: eval {B_ev / dt_ev:.1f} triples/s "
                     f"({dt_ev * 1e3:.0f} ms / batch of {B_ev})")
    srv.shutdown()
    return out


def run_w2v(V=800_000, d=128, B=8192, N=5, steps=24):
    import adapm_tpu
    from adapm_tpu.models.sgns import build_alias_table, sgns_loss, syn1_key
    from adapm_tpu.ops import DeviceRoutedRunner

    progress(f"w2v: building server ({2 * V} keys x {2 * d} f32)")
    srv = adapm_tpu.setup(2 * V, 2 * d, opts=_sys_opts(2 * V))
    bulk_device_init(srv.stores[0], d, 0.05, seed=1)
    w = srv.make_worker(0)
    counts = 1.0 / (np.arange(V) + 10.0)  # zipf corpus frequencies
    runner = DeviceRoutedRunner(
        srv, sgns_loss, role_class={"center": 0, "ctx": 0, "neg": 0},
        role_dim={k: d for k in ("center", "ctx", "neg")},
        neg_role="neg", neg_shape=(B, N),
        neg_population=syn1_key(np.arange(V)),
        neg_alias=build_alias_table(counts))
    rng = np.random.default_rng(1)
    batches = [{"center": 2 * skewed(rng, V, B),
                "ctx": 2 * skewed(rng, V, B) + 1} for _ in range(4)]
    progress("w2v: compiling + warmup")
    dt = pm_loop(srv, w, runner, batches, None, 0.05, steps, warmup=3)
    srv.shutdown()
    return {"metric": "northstar_w2v_1bwords_scale", "vocab": V, "dim": d,
            "ms_per_step": round(dt * 1e3, 2),
            "pairs_per_sec": round(B / dt, 1)}


def run_w2v_app(V=800_000, sentences=8_000, sent_len=1000, d=128, B=8192,
                N=5):
    """w2v through the APP loop (VERDICT r3 item 8): corpus on disk,
    vocab build, per-sentence deterministic pair generation + subsampling
    + batching + intent readahead + device steps — the number the 1B-words
    north star actually needs, not the bare step rate."""
    import tempfile

    from adapm_tpu.apps import word2vec as w2v
    from adapm_tpu.io import text as textio

    path = os.path.join(tempfile.gettempdir(), f"ns_w2v_{V}.txt")
    if not os.path.exists(path):
        progress(f"w2v-app: generating corpus ({sentences} x {sent_len} "
                 f"tokens over {V} vocab)")
        textio.generate_synthetic_corpus(path, vocab_size=V,
                                         num_sentences=sentences,
                                         sentence_len=sent_len, seed=3)
    args = w2v.build_parser().parse_args(
        ["--data", path, "--dim", str(d), "--window", "5",
         "--negative", str(N), "--epochs", "1", "--batch_size", str(B),
         "--lr", "0.025", "--min_count", "1", "--readahead", "200",
         "--sys.sync.max_per_sec", "0"])
    progress("w2v-app: running one epoch through the app loop")
    t0 = time.perf_counter()
    w2v.run(args)
    dt = time.perf_counter() - t0
    # count the pairs the epoch actually trained (pair generation is
    # deterministic per sentence — a dry re-pass is exact and cheap with
    # the vectorized generator)
    words, counts, vocab = textio.build_vocab(path, 1)
    total = int(counts.sum())
    n_pairs = 0
    for si, sent in enumerate(textio.sentences(path, vocab)):
        c, _ = w2v._pairs_for(sent, si, args.window, args.seed, counts,
                              total, args.sample)
        n_pairs += len(c)
    progress(f"w2v-app: {n_pairs} pairs in {dt:.1f} s")
    return {"metric": "northstar_w2v_app_loop", "vocab": len(words),
            "corpus_tokens": total, "pairs": n_pairs,
            "epoch_s": round(dt, 1),
            "pairs_per_sec_app_loop": round(n_pairs / dt, 1)}


def run_mf(users=162_541, movies=59_047, rank=128, B=16_384, steps=24,
           ratings=25_000_095):
    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.models import make_mf_loss
    from adapm_tpu.ops import DeviceRoutedRunner

    K = users + movies
    progress(f"mf: building server ({K} keys x {2 * rank} f32)")
    srv = adapm_tpu.setup(K, 2 * rank, opts=_sys_opts(K))
    bulk_device_init(srv.stores[0], rank, 0.1, seed=2)
    w = srv.make_worker(0)
    runner = DeviceRoutedRunner(
        srv, make_mf_loss(l2=0.01), role_class={"w": 0, "h": 0},
        role_dim={"w": rank, "h": rank})
    rng = np.random.default_rng(2)
    batches = [{"w": skewed(rng, users, B),
                "h": users + skewed(rng, movies, B)} for _ in range(4)]
    aux = [rng.random(B).astype(np.float32) * 4 + 1 for _ in range(4)]
    progress("mf: compiling + warmup")
    dt = pm_loop(srv, w, runner, batches, aux, 0.05, steps, warmup=3)
    srv.shutdown()
    return {"metric": "northstar_mf_movielens25m_scale",
            "users": users, "movies": movies, "rank": rank,
            "ms_per_step": round(dt * 1e3, 2),
            "ratings_per_sec": round(B / dt, 1),
            "derived_epoch_s_25M_ratings": round(dt * ratings / B, 1)}


def main():
    global TIER
    argv = [a for a in sys.argv[1:]
            if a not in ("--epoch", "--eval", "--tier")]
    full_epoch = "--epoch" in sys.argv[1:]
    do_eval = "--eval" in sys.argv[1:]
    TIER = "--tier" in sys.argv[1:]
    which = argv or ["kge", "w2v", "mf"]
    runs = {"kge": lambda: run_kge(full_epoch=full_epoch, do_eval=do_eval),
            "w2v": run_w2v, "w2v_app": run_w2v_app, "mf": run_mf}
    if os.environ.get("ADAPM_NS_SMOKE", "0").lower() not in \
            ("", "0", "false"):
        # CPU smoke of every measurement path at toy scale: keeps the
        # scripts runnable-first-try when the chip comes back (the r4
        # round lost its TPU window partly to rediscovering breakage)
        runs = {
            "kge": lambda: run_kge(E=20_000, R=20, d=16, B=256, N=4,
                                   steps=6, train_triples=10_000,
                                   full_epoch=full_epoch, do_eval=do_eval),
            "w2v": lambda: run_w2v(V=5_000, d=16, B=512, N=3, steps=6),
            "w2v_app": lambda: run_w2v_app(V=2_000, sentences=200,
                                           sent_len=80, d=16, B=512),
            "mf": lambda: run_mf(users=2_000, movies=1_000, rank=8,
                                 B=1024, steps=6),
        }
    for name in which:
        out = runs[name]()
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
