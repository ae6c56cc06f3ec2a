#!/usr/bin/env python3
"""Where a serving process settles: one run of a bag cell with NO warm-up
traffic, its window (`--seconds`, 90 by default) cut into bins of
`--bin` seconds by due time, and for each bin the median request latency
normalised by size (latency x 264 / samples: the median request of the
log-uniform grid), its median ratio to a line fitted through the
window's second half (latency = a + b x samples: 1.0 where the process
serves as it will), and what the process did in it.

    python scripts/settle_series.py --workload <a bag cell> --seed <n>
        [--seconds 90] [--bin 10] [--rate R] [benchmarks/run.py arguments]

It is `benchmarks/run.py` itself (set-up, window, checks, result line)
with `serve_open_bags.drive` wrapped: the warm-up's call serves nothing,
and the window's call is followed by one `settle_series {...}` line on
stderr (and into SETTLE_SERIES_OUT where that names a file): per bin the
requests, the normalised and the raw median, the ratio to the settled
line, the growth of the serve and tier histograms (mean ms an
observation), the rows promoted, the process's CPU seconds, and two
probes of the HOST taken once a second beside the traffic (a fixed
interpreter loop, a fixed random gather: `_Probe`). The file also gets
EVERY REQUEST: its due second, samples and latency, the stamps the
program puts on it (`t_call` .. `t_deliver`: where in the request the
time went), how many requests its client thread had carried before
(`drive` starts a new thread a client a call), and each batch's cold
members. `--rate R` serves at R a second, not the traffic file's rate.
PERF.md section 6 (PR 46) reads from it the second at which the series
is flat and which phase was not (a client thread's FIRST request spends
1.3 ms more before it is queued: the step a turn of the 64 threads
after the first request). By hand, on the chip (through `chiprun`);
`--rehearse-cpu` debugs it here.
"""
import json
import os
import resource
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)

MEDIAN_SAMPLES = 264
HISTS = ("serve.lookup_s", "serve.admit_s", "serve.queue_s",
         "serve.batch_wait_s", "serve.dispatch_s", "serve.bag_plan_s",
         "serve.bag_route_s", "serve.copy_out_s", "serve.deliver_s",
         "serve.wake_s", "serve.lock_wait_s", "tier.cold_stage_s",
         "tier.pass_s", "tier.commit_s")
STAMPS = ("t_call", "t0", "t_claim", "t_dispatch", "t_enqueued",
          "t_copied", "t_deliver")


class _Probe(threading.Thread):
    """What the HOST does beside the program, once a second: a fixed
    piece of interpreter work (a sum over a range) and a fixed piece of
    memory work (a gather of 16,384 random rows from 256 MB of its own),
    each timed. A series that settles while these stand still is the
    program's; one that settles with them is the machine's."""

    def __init__(self):
        super().__init__(daemon=True)
        rng = np.random.default_rng(0)
        self.mem = rng.random(1 << 25)                   # 256 MB, touched
        self.at = rng.integers(0, len(self.mem), 1 << 14)
        self.rows = []
        self.stop = threading.Event()

    def run(self):
        while not self.stop.wait(1.0):
            t0 = time.perf_counter()
            sum(range(20000))
            t1 = time.perf_counter()
            self.mem[self.at].sum()
            t2 = time.perf_counter()
            self.rows.append((t0, (t1 - t0) * 1e3, (t2 - t1) * 1e3))


def _per_request(t_start, res, sizes, stamps, calls, colds) -> dict:
    """Every request's stamps (seconds from the window's start), the
    thread that carried it and how many requests that thread had
    carried before, and its batch's cold members; matched by time (a
    request's `t_call` follows the client's `issued` by microseconds)."""
    issued = res["issued"] + t_start
    order = np.argsort(issued)
    st = np.array(sorted(stamps)) if stamps else np.zeros((0, 8))
    out = {"due_s": res["due"].round(4).tolist(), "samples": sizes.tolist(),
           "latency_ms": ((res["done"] - res["due"]) * 1e3).round(3).tolist(),
           "issued_s": res["issued"].round(5).tolist()}
    if len(st) == len(issued):
        for j, name in enumerate(STAMPS):
            col = np.empty(len(issued))
            col[order] = st[:, j] - t_start
            out[name + "_s"] = col.round(5).tolist()
        keys = np.empty(len(issued))
        keys[order] = st[:, 7]
        out["keys"] = keys.astype(int).tolist()
    calls = sorted(calls)
    if len(calls) == len(issued):
        seen, used = {}, np.empty(len(issued), dtype=int)
        for k, (_, ident) in enumerate(calls):
            used[order[k]] = seen.get(ident, 0)
            seen[ident] = seen.get(ident, 0) + 1
        out["thread_uses_before"] = used.tolist()
    out["cold_batches"] = [[round(t - t_start, 5), n, c]
                           for t, n, c in colds]
    return out


def _reading(srv) -> dict:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"t": time.perf_counter(), "minflt": ru.ru_minflt,
           "cpu_s": ru.ru_utime + ru.ru_stime, "nvcsw": ru.ru_nvcsw,
           "nivcsw": ru.ru_nivcsw, "load1": os.getloadavg()[0]}
    for name in HISTS + ("tier.promotions",):
        m = srv.obs.find(name)
        if m is not None:
            out[name] = m.snap()
    return out


def _bins(res, sizes, readings, width: float) -> list:
    lat = (res["done"] - res["due"]) * 1e3
    norm = lat * MEDIAN_SAMPLES / sizes
    # the settled line: least squares over the window's second half
    late = res["due"] >= res["due"][-1] / 2
    b, a = np.polyfit(sizes[late], lat[late], 1)
    ratio = lat / (a + b * sizes)
    rows = []
    for i in range(len(readings) - 1):
        a, b = readings[i], readings[i + 1]
        m = (res["due"] >= i * width) & (res["due"] < (i + 1) * width)
        row = {"from_s": i * width, "requests": int(m.sum()),
               "p50_norm_ms": float(np.median(norm[m])) if m.any() else None,
               "p50_ms": float(np.median(lat[m])) if m.any() else None,
               "p50_to_settled": round(float(np.median(ratio[m])), 4)
               if m.any() else None,
               "minflt": b["minflt"] - a["minflt"],
               "cpu_s": round(b["cpu_s"] - a["cpu_s"], 3),
               "nvcsw": b["nvcsw"] - a["nvcsw"],
               "nivcsw": b["nivcsw"] - a["nivcsw"], "load1": b["load1"]}
        for name in HISTS:
            if name in b and b[name]["count"] > a[name]["count"]:
                n = b[name]["count"] - a[name]["count"]
                row[name[:-2] + "_ms"] = round(
                    (b[name]["sum"] - a[name]["sum"]) / n * 1e3, 3)
                row[name[:-2] + "_n"] = n
        if "tier.promotions" in b:
            row["promotions"] = b["tier.promotions"] - a["tier.promotions"]
        rows.append(row)
    return rows


def main(argv):
    width, seconds, rate = 10.0, "90", None
    for flag in ("--bin", "--seconds", "--rate"):
        if flag in argv:
            i = argv.index(flag)
            value = argv[i + 1]
            del argv[i:i + 2]
            if flag == "--bin":
                width = float(value)
            elif flag == "--rate":
                rate = float(value)     # not the traffic file's
            else:
                seconds = value
    import run
    from drivers import serve_open_bags as bags
    drive = bags.drive

    def series(state, sched, deadline_ms, keep=()):
        if not keep:
            # the warm-up's call (and a sweep's): nothing is served
            # (two requests of no time: `summarize` halves what it gets)
            z = np.zeros(2)
            return {"t0": 0.0, "t1": 0.0, "due": z, "issued": z, "done": z,
                    "err": np.zeros(2, dtype=bool), "kept": {}}
        srv = state["srv"]
        # every request's stamps, carrier thread and cold members
        from adapm_tpu.serve.batcher import LookupBatcher
        from adapm_tpu.tier import coldpath
        stamps, calls, colds = [], [], []
        noted, split = LookupBatcher._note_delivered, coldpath.split_owner

        def note(self, r, now):
            stamps.append(tuple(getattr(r, n) for n in STAMPS)
                          + (len(r.keys),))
            return noted(self, r, now)

        def split_counted(store, o_sh, o_sl):
            out = split(store, o_sh, o_sl)
            colds.append((time.perf_counter(), len(out[1]),
                          int(out[1].sum())))
            return out
        LookupBatcher._note_delivered = note
        coldpath.split_owner = split_counted
        for sess in state["sessions"]:
            def call(*a, _f=sess.lookup_bags, **k):
                calls.append((time.perf_counter(), threading.get_ident()))
                return _f(*a, **k)
            sess.lookup_bags = call
        probe = _Probe()
        probe.start()
        readings = [_reading(srv)]
        stop = threading.Event()

        def sample():
            t0 = readings[0]["t"] + 0.05     # `drive`'s own start
            while not stop.wait(max(0.0, t0 + len(readings) * width
                                    - time.perf_counter())):
                readings.append(_reading(srv))
        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()
        res = drive(state, sched, deadline_ms, keep)
        stop.set()
        probe.stop.set()
        sampler.join()
        probe.join()
        readings.append(_reading(srv))
        LookupBatcher._note_delivered = noted
        coldpath.split_owner = split
        sizes = np.diff(sched["off"])
        rows = _bins(res, sizes, readings, width)
        for i, row in enumerate(rows):
            at = [p for p in probe.rows
                  if i * width <= p[0] - res["t0"] < (i + 1) * width]
            if at:
                row["probe_py_ms"] = round(float(np.median(
                    [p[1] for p in at])), 4)
                row["probe_mem_ms"] = round(float(np.median(
                    [p[2] for p in at])), 4)
        line = "settle_series " + json.dumps({
            "rate_per_s": sched["n"] / float(sched["due"][-1]),
            "bin_s": width, "bins": rows})
        print(line, file=sys.stderr, flush=True)
        if os.environ.get("SETTLE_SERIES_OUT"):
            with open(os.environ["SETTLE_SERIES_OUT"], "a") as f:
                f.write(line + "\n" + json.dumps(_per_request(
                    res["t0"], res, sizes, stamps, calls, colds)) + "\n")
        return res
    bags.drive = series
    if rate is not None:
        schedule = bags.schedule
        bags.schedule = lambda ctx, state, _, *a, **k: \
            schedule(ctx, state, rate, *a, **k)
    return run.main(argv + ["--seconds", seconds])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
