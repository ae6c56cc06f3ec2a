"""Training through the CTR app's own loop: `apps.ctr.train(run)`, timed
around one call of it. The harness owns no copy of that loop: it builds
the run the way `open_run` does, and sets `--epochs` and `--max_runtime`
on the run's arguments."""
from __future__ import annotations

import gc
import re
import sys
import time

import numpy as np

from common import read_rows, say
from drivers import _ctr, _exact_checks

_EPOCH = re.compile(r"\[ctr\] epoch \d+: loss=(\S+)")


def _train(ctx, state, epochs: int, max_runtime: float):
    """One call of the app's train(run); returns (t0, t1, pass losses)."""
    from adapm_tpu.apps import ctr
    run = state["run"]
    run.args.epochs, run.args.max_runtime = epochs, max_runtime
    mark = len(ctx.program_lines)
    t0 = time.perf_counter()
    ctr.train(run)
    t1 = time.perf_counter()
    losses = [float(m.group(1)) for ln in ctx.program_lines[mark:]
              for m in [_EPOCH.search(ln)] if m]
    return t0, t1, losses


def setup(ctx) -> dict:
    try:
        from adapm_tpu.apps import ctr  # noqa: F401
    except ImportError:
        # a checkout from before the app existed
        print("train_app_ctr: this checkout has no adapm_tpu.apps.ctr; the "
              "cell cannot run on it", file=sys.stderr)
        raise SystemExit(2)
    cfg = ctx.cfg
    data = _ctr.draw_examples(cfg, ctx.seed, cfg["examples_per_pass"],
                              "train")
    run = _ctr.build_run(ctx, data)
    state = {"run": run, "srv": run.srv}
    rows_of_class = _ctr.make_rows(ctx)
    _ctr.table_is_seeded(ctx, run, rows_of_class, ctx.checks)

    # the first steps of the timed object, through the window's own
    # call: one train(run) pass over one batch of examples each
    batches = _ctr.probe_examples(cfg, ctx.seed)
    probe = _ctr.CtrProbe(cfg, len(batches), rows_of_class)
    rec = _ctr.CtrStepRecorder(run.device_runner(run.workers[0].shard))
    try:
        for i, batch in enumerate(batches):
            run.set_examples(*batch)
            _train(ctx, state, 1, 0.0)
            if len(rec.steps) != i + 1:
                raise RuntimeError(
                    f"probe pass {i} drove {len(rec.steps) - i} steps of "
                    f"worker 0's runner, expected 1")
            probe.note_step(rec.steps[i],
                            lambda ks, cols: read_rows(run.srv, ks, cols))
    finally:
        rec.remove()
        run.set_examples(*data)
    state["probe"] = probe
    # a traced run names the step's matrix products for its reader
    state["matmul_ops"] = _ctr.matmul_ops(rec) if ctx.trace else None
    say(f"probe: {len(batches)} steps recorded, losses "
        f"{[s['loss'] for s in probe.steps]}")
    # one whole pass as warm-up: every shape of the window
    _train(ctx, state, 1, 0.0)
    # as the MF cell: at a pass end nothing is in flight, so a pause of
    # the host is a pause of the chip; what set-up left is collected now
    # and kept out of later collections
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
    rate = passes * ctx.cfg["examples_per_pass"] / (t1 - t0)
    say(f"window: {passes} passes, {steps} steps in {t1 - t0:.3f} s "
        f"({(t1 - t0) / max(steps, 1) * 1e3:.3f} ms/step)")
    return {"attempted": steps, "failed": 0, "steps": steps,
            "t0": t0, "t1": t1, "losses": losses,
            "matmul_ops": state["matmul_ops"],
            "metrics": {"train_examples_per_s": rate}}


def check(ctx, state, out, checks) -> None:
    run = state["run"]
    checks.add("passes_finished", len(out["losses"]), 1,
               ok=len(out["losses"]) >= 1)
    _exact_checks.after_window(
        ctx, run.srv, run.workers,
        np.arange(run.n_feat, dtype=np.int64), out, checks)
    _ctr.acked_push_dense(ctx, run, checks)
    state["probe"].compare(checks, ctx.traffic["probe_limits"], ctx.control)


def close(ctx, state) -> None:
    state["run"].srv.shutdown()
