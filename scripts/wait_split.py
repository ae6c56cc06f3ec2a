#!/usr/bin/env python3
"""One run of a benchmark cell with the window's growth of every
histogram of the program's registry printed beside the result line:
for each span its whole seconds, and where it has a work histogram its
work and the waits beneath it (whole - work), per dispatched step.

    python scripts/wait_split.py [--block] <benchmarks/run.py arguments>

`--block` is the calibration of PERF.md section 3: every step or scan
dispatch and every `drive_rounds` first waits for the device
(`srv.block()`, outside the step's spans), so each bracketed call runs
with NOTHING in flight (`fused.inflight_steps` reads 0) and what the
wait spans still hold is the host work inside the calls themselves: the
floor to add to `step_host_work_ms`. By hand, on the chip (through
`chiprun`); here `--rehearse-cpu` debugs it. The line goes to stderr
and, where WAIT_SPLIT_OUT names a file, into it.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)


def _grown(a, b):
    """Window growth of one histogram snapshot, or None."""
    if not isinstance(b, dict) or "count" not in b:
        return None
    a = a if isinstance(a, dict) else {"count": 0, "sum": 0.0,
                                       "buckets": [0] * len(b["buckets"])}
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    return {"count": n, "sum": b["sum"] - a["sum"],
            "buckets": [y - x for x, y in zip(a["buckets"], b["buckets"])]}


def split(obs0, obs1):
    """{span: {...}} of the window: seconds, count, and work / waits
    where the span has a work histogram; ms a dispatched step."""
    grown = {n: g for n in obs1
             if (g := _grown(obs0.get(n), obs1[n])) is not None}
    steps = grown.get("fused.dispatch_s", {}).get("count", 0)
    out = {"dispatches": steps}
    for name, g in sorted(grown.items()):
        if name.endswith("_work_s") or not name.endswith("_s"):
            continue
        row = {"n": g["count"], "s": round(g["sum"], 6)}
        work = grown.get(name[:-2] + "_work_s")
        if work is not None:
            row["work_s"] = round(work["sum"], 6)
            row["waits_s"] = round(g["sum"] - work["sum"], 6)
        if steps:
            row["ms_a_step"] = round(g["sum"] / steps * 1e3, 4)
            if work is not None:
                row["work_ms_a_step"] = round(work["sum"] / steps * 1e3, 4)
        out[name[:-2]] = row
    depth = grown.get("fused.inflight_steps")
    if depth is not None:
        out["fused.inflight_steps"] = {
            "n": depth["count"], "mean": depth["sum"] / depth["count"],
            "bounds": obs1["fused.inflight_steps"]["bounds"],
            "buckets": depth["buckets"]}
    return out


def block_before_every_dispatch():
    from adapm_tpu.core.kv import Server
    from adapm_tpu.ops.fused import DeviceRoutedRunner

    def blocked(fn, server_of):
        def wrapper(self, *a, **kw):
            server_of(self).block()
            return fn(self, *a, **kw)
        return wrapper
    for name in ("__call__", "run_scan"):
        setattr(DeviceRoutedRunner, name, blocked(
            getattr(DeviceRoutedRunner, name), lambda r: r.server))
    Server.drive_rounds = blocked(Server.drive_rounds, lambda s: s)


def main(argv):
    block = "--block" in argv
    argv = [a for a in argv if a != "--block"]
    import run
    seen = []
    obs = run._obs

    def keep(srv):
        seen.append(obs(srv))
        return seen[-1]
    run._obs = keep
    if block:
        block_before_every_dispatch()
    rc = run.main(argv)
    if len(seen) >= 2:
        line = "wait_split " + json.dumps(
            {"block": block, **split(seen[0], seen[1])})
        print(line, file=sys.stderr, flush=True)
        if os.environ.get("WAIT_SPLIT_OUT"):
            with open(os.environ["WAIT_SPLIT_OUT"], "a") as f:
                f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
