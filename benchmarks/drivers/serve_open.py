"""Open-loop lookups against `ServePlane`: requests fall due on a seeded
Poisson schedule at the rate fixed in the traffic file, client threads
issue each when it is due, and a request's time runs from the instant it
was DUE to the instant its rows are in host memory. The store is the KGE
store, filled from the seed and read-only, so every served row can be
compared with the reference's row of that key."""
from __future__ import annotations

import threading
import time

import numpy as np

from common import OUT, Zipf, percentile, rng_for, say
from drivers import _kge, _exact_checks


def schedule(ctx, rate: float, seconds: float, stream: str) -> dict:
    """n = rate x seconds requests. Every seed gets the same inter-arrival
    gaps (the exponential's quantiles) and the same request sizes (a
    log-uniform grid), each in another order, so a seed changes the order
    of the work and not its amount; the keys are Zipf draws."""
    tr, cfg = ctx.traffic, ctx.cfg
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(ctx.seed, stream)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    due = np.cumsum(gaps)
    due *= seconds * (1 - 0.5 / n) / due[-1]
    lo, hi = tr["keys_per_request"]["min"], tr["keys_per_request"]["max"]
    sizes = rng.permutation(
        np.rint(lo * (hi / lo) ** q).astype(np.int64))
    off = np.concatenate([[0], np.cumsum(sizes)])
    zipf = Zipf(cfg["num_entities"], tr["key_popularity"]["exponent"],
                rng_for(ctx.seed, "entperm"))
    return {"n": n, "due": due, "off": off,
            "keys": zipf.draw(rng, int(off[-1]))}


def drive(state, sched: dict, deadline_ms: float, keep=()) -> dict:
    """Issue the schedule from the client threads; returns per-request
    due/issued/done times (seconds from the start), failures, and the rows
    of the requests in `keep`."""
    from adapm_tpu.serve import DeadlineExceededError, ServeOverloadError
    import jax
    n, due, off, keys = (sched[k] for k in ("n", "due", "off", "keys"))
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
                with jax.profiler.TraceAnnotation("bench.lookup"):
                    rows = sess.lookup(keys[off[i]:off[i + 1]],
                                       deadline_ms=deadline_ms)
                if i in keep:
                    kept[i] = rows
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


def summarize(res: dict, deadline_ms: float) -> dict:
    lat = (res["done"] - res["due"]) * 1e3
    failed = res["err"] | (lat > deadline_ms)
    ok = lat[~failed]
    late = (res["issued"] - res["due"]) * 1e3
    half = len(lat) // 2
    # where in the window the failures fell: one count per second of due
    # time (a stall shows as a burst, an overload as a ramp)
    by_second = np.bincount(res["due"][failed].astype(int),
                            minlength=int(res["due"][-1]) + 1).tolist()
    return {"attempted": len(lat), "failed": int(failed.sum()),
            "failed_by_second": by_second,
            "p50": percentile(ok, 50) if len(ok) else None,
            "p95": percentile(ok, 95) if len(ok) else None,
            "late_p95": percentile(late, 95),
            "p50_first_half": percentile(lat[:half], 50),
            "p50_second_half": percentile(lat[half:], 50),
            "span_s": res["t1"] - res["t0"]}


def setup(ctx) -> dict:
    from adapm_tpu.serve import ServePlane
    tr = ctx.traffic
    run = _kge.build_run(ctx, np.zeros((1, 3), dtype=np.int64))
    srv = run.srv
    keys_all = np.arange(run.E + run.R, dtype=np.int64)
    make_rows = _kge.make_rows(ctx)
    _exact_checks.table_is_seeded(ctx, srv, keys_all, make_rows, ctx.checks)
    plane = ServePlane(srv)
    sessions = [plane.session() for _ in range(tr["client_threads"])]
    state = {"run": run, "srv": srv, "plane": plane, "sessions": sessions,
             "workers": run.workers, "keys_all": keys_all,
             "make_rows": make_rows}
    # every gather bucket a micro-batch's union can fall in
    cap = min(srv.opts.serve_max_batch * tr["keys_per_request"]["max"],
              run.E)
    rng = rng_for(ctx.seed, "warm")
    nkeys = 8
    while nkeys <= cap:
        sessions[0].lookup(rng.choice(run.E, nkeys, replace=False),
                           deadline_ms=600_000)
        nkeys *= 2
    warm = drive(state, schedule(ctx, tr["rate_per_s"],
                                 tr["warmup_seconds"], "warmreq"),
                 tr["deadline_ms"])
    say(f"warm-up traffic: {summarize(warm, tr['deadline_ms'])}")
    state["sched"] = schedule(ctx, tr["rate_per_s"], ctx.seconds, "req")
    sizes = np.diff(state["sched"]["off"])
    keep = set(rng_for(ctx.seed, "keep").choice(
        state["sched"]["n"], min(tr["sample_requests"], state["sched"]["n"]),
        replace=False).tolist())
    keep.add(int(np.argmax(sizes)))       # the longest request
    state["keep"] = sorted(keep)
    return state


def window(ctx, state) -> dict:
    tr = ctx.traffic
    if ctx.sweep_rates:
        for rate in ctx.sweep_rates:
            res = drive(state, schedule(ctx, rate, ctx.seconds,
                                        f"sw{rate:g}"), tr["deadline_ms"])
            print(f"sweep rate={rate:g}/s "
                  f"{summarize(res, tr['deadline_ms'])}", file=OUT,
                  flush=True)
    res = drive(state, state["sched"], tr["deadline_ms"], state["keep"])
    s = summarize(res, tr["deadline_ms"])
    say(f"window: {s}")
    return {"attempted": s["attempted"], "failed": s["failed"],
            "t0": res["t0"], "t1": res["t1"], "losses": [],
            "kept": res["kept"], "late_p95_ms": s["late_p95"],
            "p95_ms": s["p95"], "metrics": {"lookup_p50_ms": s["p50"]}}


def check(ctx, state, out, checks) -> None:
    sched, make_rows = state["sched"], state["make_rows"]
    rows_bad = rows_seen = 0
    for i, rows in sorted(out["kept"].items()):
        ks = sched["keys"][sched["off"][i]:sched["off"][i + 1]]
        ref = make_rows(ks)
        got = np.asarray(rows)
        rows_seen += len(ks)
        rows_bad += len(ks) if got.shape != ref.shape else \
            int((got != ref).any(axis=1).sum())
    checks.add("served_requests_compared", len(out["kept"]), 1,
               ok=len(out["kept"]) >= min(16, len(state["keep"])))
    print(f"served rows compared with the reference: {rows_seen}",
          file=OUT, flush=True)
    checks.add("served_rows_differ", rows_bad, 0)
    _exact_checks.after_window(ctx, state["srv"], state["workers"],
                               state["keys_all"], out, checks)


def close(ctx, state) -> None:
    state["plane"].close()
    state["srv"].shutdown()
