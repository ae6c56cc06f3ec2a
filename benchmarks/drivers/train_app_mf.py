"""Training through the MF app's own loop: `apps.matrix_factorization
.train(run)`, timed around one call of it. The harness owns no copy of
that loop: it builds the run the way `open_run` does, and sets `--epochs`
and `--max_runtime` on the run's arguments."""
from __future__ import annotations

import gc
import re
import sys
import time

import numpy as np

from common import read_rows, say
from drivers import _exact_checks, _mf

_EPOCH = re.compile(r"\[mf\] epoch \d+: loss=(\S+)")


def _train(ctx, state, epochs: int, max_runtime: float):
    """One call of the app's train(run); returns (t0, t1, pass losses)."""
    from adapm_tpu.apps import matrix_factorization as mf
    run = state["run"]
    run.args.epochs, run.args.max_runtime = epochs, max_runtime
    mark = len(ctx.program_lines)
    t0 = time.perf_counter()
    mf.train(run)
    t1 = time.perf_counter()
    losses = [float(m.group(1)) for ln in ctx.program_lines[mark:]
              for m in [_EPOCH.search(ln)] if m]
    return t0, t1, losses


def setup(ctx) -> dict:
    from adapm_tpu.apps import matrix_factorization as mf
    if not hasattr(mf, "MfRun"):
        # a checkout from before the app could be held to a window
        print("train_app_mf: this checkout's MF app has no MfRun / "
              "train(run); the cell cannot run on it", file=sys.stderr)
        raise SystemExit(2)
    cfg = ctx.cfg
    points = _mf.draw_points(cfg, ctx.seed, cfg["nnz"], "train")
    run = _mf.build_run(ctx, points)
    state = {"run": run, "srv": run.srv}
    keys_all = np.arange(run.m + run.n, dtype=np.int64)
    make_rows = _mf.make_rows(ctx)
    _exact_checks.table_is_seeded(ctx, run.srv, keys_all, make_rows,
                                  ctx.checks)

    # the first steps of the timed object, through the window's own
    # call: one train(run) pass over one batch of cells each, its pass
    # end (loss walk, bold driver) included
    probe = _mf.MfProbe(cfg, make_rows, _mf.seeded_sq_sum(ctx))
    rec = _mf.MfStepRecorder(run.device_runner(run.workers[0].shard))
    try:
        for i, batch in enumerate(_mf.probe_points(cfg, ctx.seed)):
            run.set_points(*batch)
            _train(ctx, state, 1, 0.0)
            if len(rec.steps) != i + 1:
                raise RuntimeError(
                    f"probe pass {i} drove {len(rec.steps) - i} steps of "
                    f"worker 0's runner, expected 1")
            probe.note_step(rec.steps[i],
                            lambda ks, cols: read_rows(run.srv, ks, cols),
                            run.prev_loss)
    finally:
        rec.remove()
        run.set_points(*points)
    state["probe"] = probe
    say(f"probe: 3 steps recorded, losses "
        f"{[s['loss'] for s in probe.steps]}, pass losses "
        f"{probe.pass_losses}")
    # one whole pass as warm-up: every shape of the window
    _train(ctx, state, 1, 0.0)
    # twice a pass (quiesce, the loss fetch) this loop has nothing in
    # flight, so a pause of the host is a pause of the chip, and a full
    # garbage collection over set-up's objects (the compiled programs'
    # among them) is such a pause, at a moment that differs from run to
    # run. What set-up left is collected now and what survives is kept
    # out of later collections, as a long-lived trainer does after its
    # start-up (`gc.freeze`)
    gc.collect()
    gc.freeze()
    return state


def window(ctx, state) -> dict:
    run = state["run"]
    runners = [run.device_runner(w.shard) for w in run.workers]
    s0 = sum(r.steps for r in runners)
    t0, t1, losses = _train(ctx, state, 10 ** 9, float(ctx.seconds))
    steps = sum(r.steps for r in runners) - s0
    passes = len(losses)
    rate = passes * ctx.cfg["nnz"] / (t1 - t0)
    say(f"window: {passes} passes, {steps} steps in {t1 - t0:.3f} s "
        f"({(t1 - t0) / max(passes, 1):.3f} s/pass)")
    return {"attempted": steps, "failed": 0, "steps": steps,
            "t0": t0, "t1": t1, "losses": losses,
            "metrics": {"train_examples_per_s": rate}}


def check(ctx, state, out, checks) -> None:
    run = state["run"]
    checks.add("passes_finished", len(out["losses"]), 1,
               ok=len(out["losses"]) >= 1)
    _exact_checks.after_window(
        ctx, run.srv, run.workers,
        np.arange(run.m + run.n, dtype=np.int64), out, checks)
    state["probe"].compare(checks, ctx.traffic["probe_limits"], ctx.control)


def close(ctx, state) -> None:
    state["run"].srv.shutdown()
