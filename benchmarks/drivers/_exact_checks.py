"""The exact checks every driver makes: the
seeded table is on the device bit for bit, an acknowledged push is read
back exactly, every worker reads the main copy after quiesce, and nothing
compiled or went non-finite inside the window."""
from __future__ import annotations

import numpy as np

from common import rng_for


def table_is_seeded(ctx, srv, keys_all: np.ndarray, make_rows, checks):
    """Before any step: sampled rows of the device table equal the
    reference's rows bitwise."""
    ks = rng_for(ctx.seed, "tblchk").choice(keys_all, 1024, replace=False)
    got = np.asarray(srv.read_main(ks)).reshape(len(ks), -1)
    bad = int((got != make_rows(ks)).any(axis=1).sum())
    checks.add("table_rows_differ", bad, 0)


def after_window(ctx, srv, workers, keys_all: np.ndarray, out: dict,
                 checks) -> None:
    rng = rng_for(ctx.seed, "postchk")
    ks = np.sort(rng.choice(keys_all, 256, replace=False))
    w0 = workers[0]
    L = int(srv.value_lengths[ks[0]])
    srv.quiesce()
    before = np.asarray(srv.read_main(ks)).reshape(len(ks), L)
    delta = rng.uniform(-1, 1, (len(ks), L)).astype(np.float32)
    w0.wait(w0.push(ks, delta))
    srv.quiesce()
    after = np.asarray(srv.read_main(ks)).reshape(len(ks), L)
    checks.add("acked_push_rows_not_read_back",
               int((after != before + delta).any(axis=1).sum()), 0)
    bad = 0
    for w in workers:
        got = np.asarray(w.pull_sync(ks)).reshape(len(ks), L)
        bad += int(got.tobytes() != after.tobytes())
    checks.add("workers_differ_from_main", bad, 0)
    checks.add("nonfinite_losses",
               int(sum(not np.isfinite(x) for x in out["losses"])), 0)
    late = ctx.compiles.between(out["t0"], out["t1"])
    if late:
        print(f"compiled inside the window: "
              f"{sorted({e[1] for e in late})}", flush=True)
    checks.add("compiles_in_window", len(late),
               0 if ctx.cell["chips"] == 1 else float("inf"))
