"""The exact checks of a cell with several kv shards and workers: what
`_exact_checks.after_window` checks, held to what the configuration's
guarantees add: an acknowledged push from EVERY worker, to keys that hold
replicas on several shards and to keys that were relocated since set-up,
is read back exactly from every holder; nothing compiled in the window;
and the planner did not stand still."""
from __future__ import annotations

import numpy as np

from common import rng_for

GRID = 256.0   # deltas and bases are multiples of 1/256: sums are exact


def _grid(rng, shape) -> np.ndarray:
    """float32 multiples of 1/256 in [-1, 1): any sum of a few of them is
    exact in float32, whatever order the pushes merge in."""
    return (rng.integers(-256, 256, shape) / GRID).astype(np.float32)


def after_window(ctx, srv, workers, keys_all: np.ndarray, owner0,
                 out: dict, checks) -> None:
    rng = rng_for(ctx.seed, "postchk")
    srv.quiesce()
    moved = np.nonzero(srv.ab.owner[keys_all] != owner0[keys_all])[0]
    moved = keys_all[moved]
    ks = np.unique(np.concatenate([
        rng.choice(moved, min(128, len(moved)), replace=False),
        rng.choice(keys_all, 256, replace=False)]))
    L = int(srv.value_lengths[ks[0]])
    w0 = workers[0]
    base = _grid(rng, (len(ks), L))
    w0.wait(w0.set(ks, base))
    srv.quiesce()
    # every worker wants the keys for a long while: the first to ask gets
    # the main copy, the others replicas (techniques all)
    for w in workers:
        w.intent(ks, w.current_clock, w.current_clock + (1 << 20))
    srv.wait_sync()
    holders = (srv.ab.cache_slot[:, ks] >= 0).sum(axis=0)
    want = base.copy()
    for w in workers:
        delta = _grid(rng, (len(ks), L))
        w.wait(w.push(ks, delta))
        want += delta
    srv.quiesce()
    main = np.asarray(srv.read_main(ks)).reshape(len(ks), L)
    checks.add("acked_push_rows_not_read_back",
               int((main != want).any(axis=1).sum()), 0)
    bad = 0
    for w in workers:
        got = np.asarray(w.pull_sync(ks)).reshape(len(ks), L)
        bad += int(got.tobytes() != main.tobytes())
    checks.add("workers_differ_from_main", bad, 0)
    # the check means what it says only if such keys were among them
    checks.add("acked_push_keys_relocated", int(np.isin(ks, moved).sum()),
               "> 0", ok=bool(np.isin(ks, moved).any()))
    checks.add("acked_push_keys_on_2_replicas", int((holders >= 2).sum()),
               "> 0", ok=bool((holders >= 2).any()))
    checks.add("nonfinite_losses",
               int(sum(not np.isfinite(x) for x in out["losses"])), 0)
    late = ctx.compiles.between(out["t0"], out["t1"])
    if late:
        print(f"compiled inside the window: "
              f"{sorted({e[1] for e in late})}", flush=True)
    checks.add("compiles_in_window", len(late), 0)
    # liveness: a run in which the planner stood still measures nothing
    # of what the cell is for
    checks.add("relocations_in_window", out["relocations"], "> 0",
               ok=out["relocations"] > 0)
    checks.add("replicas_live", out["replicas_live"], "> 0",
               ok=out["replicas_live"] > 0)
