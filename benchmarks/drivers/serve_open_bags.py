"""Open-loop bag reads against the CTR app's serving side: requests fall
due on `serve_open`'s seeded Poisson schedule at the rate fixed in the
traffic file, each the feature keys of its samples (all members of all
bags, `_ctr.py`'s Zipf generator), client threads issue each through
`ServeSession.lookup_bags` when it is due, and a request's time runs
from the instant it was DUE to the instant its pooled vectors are in host
memory. The store is the app's `CtrServe`, filled from the seed and
read-only, so every pooled vector can be compared with the reference's
sum over that bag's seeded rows (`reference/bags_np.py`)."""
from __future__ import annotations

import sys
import threading
import time
import types

import numpy as np

from common import OUT, app_seed, fill_store_from_seed, rng_for, say
from drivers import _ctr, _exact_checks, serve_open
from reference import bags_np


def build_serve(ctx):
    """`CtrServe(args)` as `open_serve` builds it, with the pool filled on
    the device from the seed instead of `init_model()`'s host fill."""
    try:
        from adapm_tpu.apps import ctr
        from adapm_tpu.apps.ctr import CtrServe
    except ImportError:
        # a checkout from before the app had a serving side
        print("serve_open_bags: this checkout has no "
              "adapm_tpu.apps.ctr.CtrServe; the cell cannot run on it",
              file=sys.stderr)
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
    fill_store_from_seed(serve.srv, 0,
                         np.arange(serve.n_feat, dtype=np.int64),
                         cfg["embedding_dim"], cfg["init_scale"], 0.0,
                         ctx.seed)
    say(f"CtrServe: {serve.n_feat} feature keys, rows of {serve.dim} "
        f"{serve.srv.stores[0].main.dtype}, main pool "
        f"{serve.srv.stores[0].main.shape}")
    return serve


def schedule(ctx, state, rate: float, seconds: float, stream: str) -> dict:
    """`serve_open.schedule`'s due times and sizes (the same exponential
    quantiles and log-uniform grid, a size being a request's SAMPLES),
    and for every request its `lookup_bags` arguments: the members of
    its samples from `_ctr.py`'s generator (`state["zipfs"]`: per table
    the Zipf popularity over the fixed permutation of its held rows)."""
    tr, serve = ctx.traffic, state["serve"]
    sized = types.SimpleNamespace(
        seed=ctx.seed, cfg={"num_entities": 1},
        traffic={"keys_per_request": tr["samples_per_request"],
                 "key_popularity": tr["key_popularity"]})
    sched = serve_open.schedule(sized, rate, seconds, stream)
    off = sched["off"]
    # `_ctr.draw_examples`' members (it draws dense features and labels
    # besides, which a bag read has none of)
    rng = rng_for(ctx.seed, stream + "m")
    members = np.concatenate(
        [z.draw(rng, (int(off[-1]), hot)) for z, hot in
         zip(state["zipfs"], ctx.cfg["multi_hot_sizes"])], axis=1)
    args = [serve.bag_args(serve.feat_keys(members[off[i]:off[i + 1]]))
            for i in range(sched["n"])]
    return {"n": sched["n"], "due": sched["due"], "off": off, "args": args}


def drive(state, sched: dict, deadline_ms: float, keep=()) -> dict:
    """`serve_open.drive` with `lookup_bags` as the call: per-request
    due/issued/done times (seconds from the start), failures, and the
    replies of the requests in `keep`."""
    from adapm_tpu.serve import DeadlineExceededError, ServeOverloadError
    import jax
    n, due, args = sched["n"], sched["due"], sched["args"]
    issued = np.zeros(n)
    done = np.zeros(n)
    err = np.zeros(n, dtype=bool)
    kept = {}
    keep = frozenset(int(i) for i in keep)
    nxt = [0]
    lock = threading.Lock()
    t_start = time.perf_counter() + 0.05

    def client(sess):
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= n:
                return
            delay = t_start + due[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            issued[i] = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.lookup_bags"):
                    pooled = sess.lookup_bags(*args[i], pooling="sum",
                                              deadline_ms=deadline_ms)
                if i in keep:
                    kept[i] = pooled
            except (DeadlineExceededError, ServeOverloadError):
                err[i] = True
            done[i] = time.perf_counter()

    threads = [threading.Thread(target=client, args=(s,), daemon=True)
               for s in state["sessions"]]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_end = time.perf_counter()
    return {"t0": t_start, "t1": t_end, "due": due,
            "issued": issued - t_start, "done": done - t_start,
            "err": err, "kept": kept}


def _summary(res: dict, tr: dict) -> dict:
    """`serve_open.summarize` and, beside it and deciding nothing, the
    share of requests over the source's 60 ms."""
    s = serve_open.summarize(res, tr["deadline_ms"])
    lat = (res["done"] - res["due"]) * 1e3
    s["share_over_source_ms"] = float(
        (res["err"] | (lat > tr["source_latency_ms"])).mean())
    return s


def setup(ctx) -> dict:
    tr = ctx.traffic
    serve = build_serve(ctx)
    srv = serve.srv
    keys_all = np.arange(serve.n_feat, dtype=np.int64)
    cfg = ctx.cfg

    def make_rows(keys):
        return bags_np.seeded_rows(keys, cfg["embedding_dim"],
                                   cfg["init_scale"], ctx.seed)

    _exact_checks.table_is_seeded(ctx, srv, keys_all, make_rows, ctx.checks)
    # every bag program a batch of 1..max_batch requests can need
    plane = serve.open_plane()
    sessions = [plane.session() for _ in range(tr["client_threads"])]
    state = {"serve": serve, "srv": srv, "plane": plane,
             "sessions": sessions, "keys_all": keys_all,
             "zipfs": _ctr._zipfs(cfg)}
    # the window's requests are drawn BEFORE the warm-up traffic (tens of
    # millions of Zipf draws, seconds of the host): the warm-up then
    # runs into the window with nothing between them
    state["sched"] = sched = schedule(ctx, state, tr["rate_per_s"],
                                      ctx.seconds, "req")
    keep = set(rng_for(ctx.seed, "keep").choice(
        sched["n"], min(tr["sample_requests"], sched["n"]),
        replace=False).tolist())
    keep.add(int(np.argmax(np.diff(sched["off"]))))   # the longest request
    state["keep"] = sorted(keep)
    warm = drive(state, schedule(ctx, state, tr["rate_per_s"],
                                 tr["warmup_seconds"], "warmreq"),
                 tr["deadline_ms"])
    say(f"warm-up traffic: {_summary(warm, tr)}")
    return state


def window(ctx, state) -> dict:
    tr = ctx.traffic
    for rate in ctx.sweep_rates:
        res = drive(state, schedule(ctx, state, rate, ctx.seconds,
                                    f"sw{rate:g}"), tr["deadline_ms"])
        print(f"sweep rate={rate:g}/s {_summary(res, tr)}", file=OUT,
              flush=True)
    res = drive(state, state["sched"], tr["deadline_ms"], state["keep"])
    s = _summary(res, tr)
    say(f"window: {s}")
    print(f"requests over the source's {tr['source_latency_ms']:g} ms "
          f"(decides nothing): {s['share_over_source_ms']:.4f}", file=OUT,
          flush=True)
    return {"attempted": s["attempted"], "failed": s["failed"],
            "t0": res["t0"], "t1": res["t1"], "losses": [],
            "kept": res["kept"], "late_p95_ms": s["late_p95"],
            "p95_ms": s["p95"], "metrics": {"lookup_p50_ms": s["p50"]}}


def check(ctx, state, out, checks) -> None:
    """Every pooled vector of the kept requests against the reference's
    member-order float32 sum over the seeded rows, bit for bit."""
    cfg, sched = ctx.cfg, state["sched"]
    dim = cfg["embedding_dim"]
    bad = seen = 0
    for i, pooled in sorted(out["kept"].items()):
        tables, bags = sched["args"][i]
        ref = bags_np.reply(tables, bags, dim, cfg["init_scale"], ctx.seed)
        for got, (want, _), bg in zip(pooled, ref, bags):
            got = np.asarray(got)
            seen += len(bg) - 1
            bad += len(bg) - 1 if got.shape != want.shape or \
                got.dtype != want.dtype else \
                int((got != want).any(axis=1).sum())
    checks.add("served_requests_compared", len(out["kept"]), 1,
               ok=len(out["kept"]) >= min(32, len(state["keep"])))
    print(f"pooled vectors compared with the reference: {seen}",
          file=OUT, flush=True)
    checks.add("pooled_vectors_differ", bad,
               ctx.traffic["reply_limits"]["pooled_vectors_differ"])
    _exact_checks.after_window(ctx, state["srv"], state["serve"].workers,
                               state["keys_all"], out, checks)


def close(ctx, state) -> None:
    state["serve"].close()
