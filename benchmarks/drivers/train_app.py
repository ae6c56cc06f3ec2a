"""Training through the program's own loop: `apps.knowledge_graph_
embeddings.train(run)`, timed around one call of it. The harness owns no
copy of that loop: it builds the run the way `open_run` does, and sets
`--epochs` and `--max_runtime` on the run's arguments."""
from __future__ import annotations

import re
import time

import numpy as np

from common import read_rows, say
from drivers import _kge, _exact_checks
from drivers._probe import Probe, StepRecorder
from reference import complex_np

_EPOCH = re.compile(r"\[kge\] epoch \d+: loss=(\S+)")


def _train(ctx, state, epochs: int, max_runtime: float):
    """One call of the app's train(run); returns (t0, t1, pass losses)."""
    from adapm_tpu.apps import knowledge_graph_embeddings as kge
    run = state["run"]
    run.args.epochs, run.args.max_runtime = epochs, max_runtime
    mark = len(ctx.program_lines)
    t0 = time.perf_counter()
    kge.train(run)
    t1 = time.perf_counter()
    losses = [float(m.group(1)) for ln in ctx.program_lines[mark:]
              for m in [_EPOCH.search(ln)] if m]
    return t0, t1, losses


def setup(ctx) -> dict:
    cfg, B = ctx.cfg, ctx.cfg["batch_size"]
    n = cfg["train_triples"]
    train = _kge.draw_triples(cfg, ctx.seed, n, "train")
    n_probe = ctx.traffic["probe_steps"]
    probe_triples = _kge.draw_triples(cfg, ctx.seed, n_probe * B, "probe")
    run = _kge.build_run(ctx, train)
    state = {"run": run, "srv": run.srv}
    keys_all = np.arange(run.E + run.R, dtype=np.int64)
    make_rows = _kge.make_rows(ctx)
    _exact_checks.table_is_seeded(ctx, run.srv, keys_all, make_rows,
                                  ctx.checks)

    # the first steps of the timed object, through the window's own
    # call: one train(run) pass over one batch of triples each
    w0 = run.workers[0]
    runner = run.device_runner(w0.shard)
    probe = Probe(n_probe, complex_np, "neg", (B, cfg["neg_ratio"]),
                  run.ekey(np.arange(run.E)), None, run.ent_dim,
                  lambda ks: (ks >= run.E).astype(np.int64),
                  ["entity", "relation"], make_rows, cfg["lr"])
    rec = StepRecorder(runner)
    try:
        for i in range(n_probe):
            run.ds.train = probe_triples[i * B:(i + 1) * B]
            _train(ctx, state, 1, 0.0)
            if len(rec.steps) != i + 1:
                raise RuntimeError(
                    f"probe pass {i} drove {len(rec.steps) - i} steps of "
                    f"worker 0's runner, expected 1")
            probe.note_step(rec.steps[i],
                            lambda ks, cols: read_rows(run.srv, ks, cols))
    finally:
        rec.remove()
        run.ds.train = train
    state["probe"] = probe
    say(f"probe: {n_probe} steps recorded, losses "
        f"{[s['loss'] for s in probe.steps]}")
    # one whole pass as warm-up: every shape of the window
    _train(ctx, state, 1, 0.0)
    return state


def window(ctx, state) -> dict:
    run = state["run"]
    runners = [run.device_runner(w.shard) for w in run.workers]
    s0 = sum(r.steps for r in runners)
    t0, t1, losses = _train(ctx, state, 10 ** 9, float(ctx.seconds))
    steps = sum(r.steps for r in runners) - s0
    passes = len(losses)
    rate = passes * ctx.cfg["train_triples"] / (t1 - t0)
    say(f"window: {passes} passes, {steps} steps in {t1 - t0:.3f} s "
        f"({(t1 - t0) / max(steps, 1) * 1e3:.3f} ms/step)")
    return {"attempted": steps, "failed": 0, "steps": steps,
            "t0": t0, "t1": t1, "losses": losses,
            "metrics": {"train_examples_per_s": rate}}


def check(ctx, state, out, checks) -> None:
    run = state["run"]
    checks.add("passes_finished", len(out["losses"]), 1,
               ok=len(out["losses"]) >= 1)
    _exact_checks.after_window(
        ctx, run.srv, run.workers,
        np.arange(run.E + run.R, dtype=np.int64), out, checks)
    state["probe"].compare(checks, ctx.traffic["limits"], ctx.control)


def close(ctx, state) -> None:
    state["run"].srv.shutdown()
