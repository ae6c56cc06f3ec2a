"""The KGE store as the app builds it (`KgeRun`), filled from the seed on
the device. Shared by the train and the serve driver of the KGE
configurations."""
from __future__ import annotations

import dataclasses

import numpy as np

from common import (Zipf, app_seed, fill_store_from_seed, rng_for, say,
                    table_rows)


def draw_triples(cfg: dict, seed: int, n: int, stream: str) -> np.ndarray:
    """n triples: subjects and objects Zipf over a seeded permutation of
    the entity ids, relations uniform."""
    E, R = cfg["num_entities"], cfg["num_relations"]
    rng = rng_for(seed, stream)
    zipf = Zipf(E, cfg["assumed"]["entity_zipf_exponent"],
                rng_for(seed, "entperm"))
    return np.stack([zipf.draw(rng, n), rng.integers(0, R, n),
                     zipf.draw(rng, n)], axis=1).astype(np.int64)


def build_run(ctx, train: np.ndarray):
    """`KgeRun(args, ds)` over `train`, as `open_run` builds it, with the
    table filled on the device from the seed instead of `init_model()`'s
    host fill, and with the store options of the configuration's `sys`.

    The app has no flag for `cache_slots_per_shard` (its auto value gives
    a single shard two replica pools the size of the table, 28.6 GiB in
    all), so the one option is set where the app reads its options: its
    `make_server`. PERF.md lists the missing flag under Open questions."""
    import adapm_tpu
    from adapm_tpu.apps import knowledge_graph_embeddings as kge
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.io.kge import TripleDataset
    cfg = ctx.cfg
    argv = ["--model", cfg["model"], "--dim", str(cfg["dim"]),
            "--batch_size", str(cfg["batch_size"]),
            "--neg_ratio", str(cfg["neg_ratio"]), "--lr", str(cfg["lr"]),
            "--num_shards", str(cfg["kv_shards"]),
            "--num_workers", str(cfg["workers"]),
            "--eval_every", "0", "--epochs", "1",
            "--seed", str(app_seed(ctx.seed))] + list(cfg["app_args"])
    args = kge.build_parser().parse_args(argv)

    def make_server(args, num_keys, value_lengths, num_workers):
        opts = dataclasses.replace(SystemOptions.from_args(args),
                                   **cfg["sys"])
        return adapm_tpu.setup(num_keys, value_lengths, opts=opts,
                               num_shards=args.num_shards or None,
                               num_workers=num_workers)

    app_make_server, kge.make_server = kge.make_server, make_server
    try:
        run = kge.KgeRun(args, TripleDataset(
            cfg["num_entities"], cfg["num_relations"], train))
    finally:
        kge.make_server = app_make_server
    keys = np.arange(run.E + run.R, dtype=np.int64)
    fill_store_from_seed(run.srv, run.ent_class, keys, run.ent_dim,
                         cfg["init_scale"], cfg["adagrad_init"], ctx.seed)
    # what open_run does after init_model(): uniform negatives over the
    # entities
    run.srv.enable_sampling_support(
        lambda n, r: run.ekey(r.integers(0, run.E, n)),
        allowed_keys=run.ekey(np.arange(run.E)))
    say(f"KgeRun: {run.E} entities + {run.R} relations, rows of "
        f"{2 * run.ent_dim} {run.srv.stores[run.ent_class].main.dtype}, "
        f"main pool {run.srv.stores[run.ent_class].main.shape}")
    return run


def make_rows(ctx):
    """keys -> the seeded rows, numpy: the reference's copy of the table."""
    cfg = ctx.cfg
    w = 2 * cfg["dim"]
    return lambda keys: table_rows(keys, 2 * w, w, cfg["init_scale"],
                                   cfg["adagrad_init"], ctx.seed)
