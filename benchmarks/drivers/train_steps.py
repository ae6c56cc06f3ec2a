"""A training step loop driven by the harness: the app's per-step body
(intent for the next batch -> fused step -> a planner round -> clock) over
a ring of seeded batches, for the configurations whose app cannot be held
to a window (`apps/word2vec.run` is one function from corpus file to
shutdown). The store is built with the options the configuration states
under `sys`."""
from __future__ import annotations

import time

import numpy as np

from common import (Zipf, app_seed, fill_store_from_seed, read_rows, rng_for,
                    say, table_rows, vose_alias)
from drivers import _exact_checks
from drivers._probe import Probe, StepRecorder
from reference import sgns_np


def setup(ctx) -> dict:
    import adapm_tpu
    import jax
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.models.sgns import sgns_loss
    from adapm_tpu.ops import DeviceRoutedRunner
    cfg, tr = ctx.cfg, ctx.traffic
    V, d, B, N = (cfg["vocabulary"], cfg["dim"], cfg["batch_size"],
                  cfg["negatives"])
    srv = adapm_tpu.setup(2 * V, 2 * d, opts=SystemOptions(**cfg["sys"]),
                          num_shards=cfg["kv_shards"],
                          num_workers=cfg["workers"])
    keys_all = np.arange(2 * V, dtype=np.int64)
    fill_store_from_seed(srv, 0, keys_all, d, cfg["init_scale"],
                         cfg["adagrad_init"], ctx.seed)
    make_rows = lambda ks: table_rows(  # noqa: E731
        ks, 2 * d, d, cfg["init_scale"], cfg["adagrad_init"], ctx.seed)
    say(f"store: {2 * V} keys, rows of {2 * d} "
        f"{srv.stores[0].main.dtype}, main pool {srv.stores[0].main.shape}")
    _exact_checks.table_is_seeded(ctx, srv, keys_all, make_rows, ctx.checks)

    # word ids are frequency ranks (word2vec sorts its vocabulary by
    # count); unigram counts are Zipf, the noise distribution count^0.75
    expo = cfg["assumed"]["unigram_zipf_exponent"]
    counts = 1.0 / np.arange(1, V + 1, dtype=np.float64) ** expo
    prob, alias = vose_alias(counts ** cfg["noise_power"])
    population = 2 * np.arange(V, dtype=np.int64) + 1   # output vectors
    w = srv.make_worker(0)
    runner = DeviceRoutedRunner(
        srv, sgns_loss, role_class={"center": 0, "ctx": 0, "neg": 0},
        role_dim={k: d for k in ("center", "ctx", "neg")},
        shard=w.shard, neg_role="neg", neg_shape=(B, N),
        neg_population=population, neg_alias=(prob, alias),
        seed=app_seed(ctx.seed))
    words = Zipf(V, expo)
    rng = rng_for(ctx.seed, "pairs")
    ring = [{"center": 2 * words.draw(rng, B),
             "ctx": 2 * words.draw(rng, B) + 1}
            for _ in range(tr["ring_batches"])]
    lr = cfg["lr"]

    def step(i: int):
        """The app's per-step body."""
        nxt = ring[(i + 1) % len(ring)]
        w.intent(np.unique(np.concatenate([nxt["center"], nxt["ctx"]])),
                 w.current_clock + 1, w.current_clock + 2)
        loss = runner(ring[i % len(ring)], None, lr)
        srv.drive_rounds(1)
        w.advance_clock()
        return loss

    state = {"srv": srv, "worker": w, "runner": runner, "step": step,
             "keys_all": keys_all, "next": 0}
    n_probe = tr["probe_steps"]
    probe = Probe(n_probe, sgns_np, "neg", (B, N), population,
                  (prob, alias, population), d,
                  lambda ks: (ks % 2).astype(np.int64), ["syn0", "syn1"],
                  make_rows, lr)
    rec = StepRecorder(runner)
    try:
        for i in range(n_probe):
            jax.block_until_ready(step(i))
            probe.note_step(rec.steps[i],
                            lambda ks, cols: read_rows(srv, ks, cols))
    finally:
        rec.remove()
    state["probe"] = probe
    say(f"probe: {n_probe} steps recorded, losses "
        f"{[s['loss'] for s in probe.steps]}")
    for i in range(n_probe, n_probe + tr["warmup_steps"]):
        loss = step(i)
    jax.block_until_ready(loss)
    state["next"] = n_probe + tr["warmup_steps"]
    return state


def window(ctx, state) -> dict:
    import jax
    step, every = state["step"], ctx.traffic["sync_every_steps"]
    i = i0 = state["next"]
    losses = []
    t0 = t1 = time.perf_counter()
    while t1 - t0 < ctx.seconds:
        for _ in range(every):
            loss = step(i)
            i += 1
        losses.append(float(jax.block_until_ready(loss)))
        t1 = time.perf_counter()
    steps = i - i0
    say(f"window: {steps} steps in {t1 - t0:.3f} s "
        f"({(t1 - t0) / steps * 1e3:.3f} ms/step)")
    rate = steps * ctx.cfg["batch_size"] / (t1 - t0)
    return {"attempted": steps, "failed": 0, "steps": steps,
            "t0": t0, "t1": t1, "losses": losses,
            "metrics": {"train_examples_per_s": rate}}


def check(ctx, state, out, checks) -> None:
    _exact_checks.after_window(ctx, state["srv"], [state["worker"]],
                               state["keys_all"], out, checks)
    state["probe"].compare(checks, ctx.traffic["limits"], ctx.control)


def close(ctx, state) -> None:
    state["srv"].shutdown()
