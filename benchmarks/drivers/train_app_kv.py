"""Training through the program's own loop on several kv shards with
several workers: `apps.knowledge_graph_embeddings.train(run)`, one call a
pass, every pass a FRESH seeded draw of triples, so that the planner keeps
relocating and replicating all through the window as it does through a
real epoch. The harness owns no copy of the loop: it builds the run the
way `open_run` does, from flags alone, and hands `train()` the next
draw."""
from __future__ import annotations

import numpy as np

from common import read_rows, rng_for, say
from drivers import _exact_checks, _exact_checks_kv, _kge
from drivers._probe_kv import LiveRows, Named, ProbeKv, StepRecorderKv
from drivers.train_app import _train
from reference import complex_np


def _pass(ctx, state):
    """train(run) over the next fresh draw; (t0, t1, the pass's loss)."""
    i = state["next_draw"]
    if i >= len(state["draws"]):
        raise RuntimeError(
            f"the window outran the {len(state['draws'])} passes drawn at "
            f"set-up: raise the traffic's passes_drawn")
    state["next_draw"] = i + 1
    state["run"].ds.train = state["draws"][i]
    return _train(ctx, state, 1, 0.0)


def _runners(run):
    return [run.device_runner(w.shard) for w in run.workers]


def _drain(run) -> None:
    """Fold the runners' device-side locality counts into the program's
    counters (`fused.rows_total`, `fused.rows_local_total`): they move
    only at a drain, and a window is shorter than the drain interval."""
    for r in _runners(run):
        r.locality_counts()


def _probe_alone(ctx, state, triples, make_rows, before=None) -> ProbeKv:
    """One step of worker 0 ALONE for each batch of `triples`, each one
    pass of the window's own call, recorded at the compiled step's
    boundary. With one worker the reference can follow step by step
    whatever the planner moves; train() is handed a one-worker view."""
    run, cfg, B = state["run"], ctx.cfg, ctx.cfg["batch_size"]
    n_steps = len(triples) // B
    w0 = run.workers[0]
    probe = ProbeKv(n_steps, complex_np, "neg", (B, cfg["neg_ratio"]),
                    run.ekey(np.arange(run.E)), None, run.ent_dim,
                    lambda ks: (ks >= run.E).astype(np.int64),
                    ["entity", "relation"], make_rows, cfg["lr"])
    workers, train = run.workers, run.ds.train
    rec = StepRecorderKv(run.device_runner(w0.shard), before)
    run.workers, run.num_workers = [w0], 1
    try:
        for i in range(n_steps):
            run.ds.train = triples[i * B:(i + 1) * B]
            _train(ctx, state, 1, 0.0)
            if len(rec.steps) != i + 1:
                raise RuntimeError(
                    f"probe pass {i} drove {len(rec.steps) - i} steps of "
                    f"worker 0's runner, expected 1")
            probe.note_step(rec.steps[i],
                            lambda ks, cols: read_rows(run.srv, ks, cols))
    finally:
        rec.remove()
        run.workers, run.num_workers = workers, len(workers)
        run.ds.train = train
    say(f"probe: {n_steps} steps recorded, losses "
        f"{[s['loss'] for s in probe.steps]}")
    return probe


def _live_probe(ctx, state, checks) -> ProbeKv:
    """The second probe, after the window: worker 0 alone again, from
    the table as the window left it. Its subjects are entities whose
    main copy has moved since set-up, its objects entities that hold a
    replica now, so the step reads and writes through the route mirrors,
    the cache and the delta pool as the planner has left them; the
    reference starts from the rows as they stand (`LiveRows`)."""
    run, cfg, B = state["run"], ctx.cfg, ctx.cfg["batch_size"]
    srv, rng = run.srv, rng_for(ctx.seed, "liveprobe")
    srv.quiesce()
    ekeys = run.ekey(np.arange(run.E))
    moved = np.nonzero(srv.ab.owner[ekeys] != state["owner0"][ekeys])[0]
    held = np.nonzero((srv.ab.cache_slot[:, ekeys] >= 0).any(axis=0))[0]
    checks.add("live_probe_entities_moved", len(moved), "> 0",
               ok=len(moved) > 0)
    checks.add("live_probe_entities_replicated", len(held), "> 0",
               ok=len(held) > 0)
    n = ctx.traffic["live_probe_steps"] * B
    everywhere = np.arange(run.E)
    triples = np.stack([
        rng.choice(moved if len(moved) else everywhere, n),
        rng.integers(0, run.R, n),
        rng.choice(held if len(held) else everywhere, n)],
        axis=1).astype(np.int64)
    live = LiveRows(lambda ks: read_rows(srv, ks),
                    (B, cfg["neg_ratio"]))
    return _probe_alone(ctx, state, triples, live, before=live.note)


def setup(ctx) -> dict:
    cfg, B = ctx.cfg, ctx.cfg["batch_size"]
    n, n_probe = cfg["train_triples"], ctx.traffic["probe_steps"]
    drawn = _kge.draw_triples(cfg, ctx.seed, ctx.traffic["passes_drawn"] * n,
                              "train")
    probe_triples = _kge.draw_triples(cfg, ctx.seed, n_probe * B, "probe")
    run = _kge.build_run(ctx, drawn[:n])
    state = {"run": run, "srv": run.srv, "next_draw": 0,
             "draws": [drawn[lo:lo + n] for lo in range(0, len(drawn), n)]}
    # every program the window's sizes can reach, before it reaches them
    ran = run.precompile()
    say(f"precompiled {ran} planner programs and the step's variants")
    keys_all = np.arange(run.E + run.R, dtype=np.int64)
    state["owner0"] = run.srv.ab.owner.copy()
    make_rows = _kge.make_rows(ctx)
    _exact_checks.table_is_seeded(ctx, run.srv, keys_all, make_rows,
                                  ctx.checks)

    # the probe: worker 0 ALONE, from the quiesced seeded table, through
    # the window's own call with intents and planner rounds live
    on_shards = np.unique(run.srv.ab.owner[
        run.ekey(probe_triples[:, [0, 2]].ravel())])
    ctx.checks.add("probe_keys_on_shards", len(on_shards), run.srv.num_shards,
                   ok=len(on_shards) == run.srv.num_shards)
    state["probe"] = _probe_alone(ctx, state, probe_triples, make_rows)
    # warm-up passes of fresh draws, all workers: the head's replicas
    # exist and every shape of the window has run
    for _ in range(ctx.traffic["warmup_passes"]):
        _pass(ctx, state)
    _drain(run)
    return state


def window(ctx, state) -> dict:
    run = state["run"]
    stats = run.srv.sync.stats
    steps0 = sum(r.steps for r in _runners(run))
    reloc0 = stats.relocations
    losses, t0, t1 = [], None, None
    while t1 is None or t1 - t0 < ctx.seconds:
        a, t1, loss = _pass(ctx, state)
        t0 = a if t0 is None else t0
        losses += loss
    steps = sum(r.steps for r in _runners(run)) - steps0
    live = sum(len(t) for t in run.srv.sync.replicas)
    _drain(run)
    rate = len(losses) * ctx.cfg["train_triples"] / (t1 - t0)
    say(f"window: {len(losses)} passes, {steps} steps in {t1 - t0:.3f} s "
        f"({(t1 - t0) / max(steps, 1) * 1e3:.3f} ms/step); "
        f"{stats.relocations - reloc0} relocations, {live} replicas live")
    return {"attempted": steps, "failed": 0, "steps": steps,
            "t0": t0, "t1": t1, "losses": losses,
            "relocations": stats.relocations - reloc0,
            "replicas_live": live,
            "metrics": {"train_examples_per_s": rate}}


def check(ctx, state, out, checks) -> None:
    run = state["run"]
    checks.add("passes_finished", len(out["losses"]), 1,
               ok=len(out["losses"]) >= 1)
    live = _live_probe(ctx, state, checks)
    _exact_checks_kv.after_window(
        ctx, run.srv, run.workers,
        np.arange(run.E + run.R, dtype=np.int64), state["owner0"], out,
        checks)
    state["probe"].compare(checks, ctx.traffic["limits"], ctx.control)
    live.compare(Named(checks, "live_"), ctx.traffic["limits"], ctx.control)


def close(ctx, state) -> None:
    state["run"].srv.shutdown()
