"""Click-through-rate training app: DLRM with a low-rank DCNv2 interaction
(models/dlrm.py; MLPerf Training's recommendation model, torchrec
`dlrm_main.py`) over multi-hot categorical features, every trainable
parameter in the parameter manager. The fused-step form of the task that
`examples/ctr_example.py` runs through the bindings and torch.

Two length classes meet in one fused step (ops/fused.py): the embedding
tables' rows, [embedding (dim) | AdaGrad (dim)], of which a step names
`sum(multi_hot_sizes)` an example, pooled into one bag a feature; and the
dense network's tensors flattened into rows [weights | AdaGrad] of
`--dense_row` weights, ALL of which every step names (a key that every
worker reads in every step is what the planner replicates on every node).
The step gathers both, the loss reshapes the gathered dense rows into its
matrices and multiplies, and AdaGrad writes both classes back.

Key layout: the tables' held rows in table order, then the dense tensors
in network order (models/dlrm.py DenseLayout).

A pass is `--examples` examples in batches of `--batch_size`, the workers
in turns. A batch (keys member-major `[members, B]`, the distinct keys of
the intent, the keys' and the dense features' upload) is prepared
`--lookahead` batches ahead of its step, with its intent, and kept: a pass
that revisits its examples (`run`, `train` called again) prepares nothing
twice, and a pass of fresh examples (`set_examples`) prepares each batch
while the steps before it run. Every batch of a worker's turn gets an
intent, the first `--lookahead` at the start of the turn. Per step: the
prepare and intent `--lookahead` batches ahead, the dispatch, the
planner's rounds, the clock. A pass ends with `quiesce()` and one fetch of
the mean of its steps' losses.

`open_run(args)` sets a run up, `train(run)` trains `--epochs` passes on it
(and can be called again), `run(args)` is both and shuts the server down.

The serving side (`CtrServe`, `open_serve(args)`): an embedding shard of a
ranking service holds the tables' rows ALONE, `--embedding_dim` floats a
key (no AdaGrad half, no dense class), behind a `ServePlane`; a request
is the feature keys of its samples in the layout a training batch has
(`[members, S]`, member-major) and is answered through
`ServeSession.lookup_bags` with one sum-pooled vector a sample and table.
The dense network's forward pass is not part of it.

Run: python -m adapm_tpu.apps.ctr --examples 4096 ...
"""
from __future__ import annotations

import argparse
import sys
from functools import partial

import jax.numpy as jnp
import numpy as np

from ..models.dlrm import DenseLayout, dense_tensors, make_dlrm_loss
from .common import (AppRun, Batch, add_common_arguments,
                     enforce_full_replication, global_worker_slices,
                     make_server, wrap_batches)


# AdaGrad's damping: a position's update is -lr g / sqrt(acc + g*g + eps).
# The other apps start their accumulators at 1e-6 under the runner's eps
# of 1e-10; this network's gradients are too small for that (the mean
# over a batch through eight layers: a feature row's g*g is 1e-16, which
# float32 cannot add to 1e-6), so the accumulators start at 0, where
# every g*g registers, and the same 1e-6 damps from here
ADAGRAD_EPS = 1e-6


def _ints(text: str) -> list:
    return [int(x) for x in text.split(",")]


def generate_synthetic(table_rows, multi_hot_sizes, num_dense: int, n: int,
                       zipf: float, click_rate: float, seed: int):
    """n examples (members [n, M] table-local row ids, dense features
    [n, num_dense], labels [n]): per table ids Zipf(`zipf`) over its rows,
    a bag's members drawn independently, dense features N(0, 1), labels
    Bernoulli of a logistic ground truth over the dense features whose
    offset puts the click rate near `click_rate`."""
    rng = np.random.default_rng(seed)
    cols = []
    for rows, hot in zip(table_rows, multi_hot_sizes):
        cdf = np.cumsum(1.0 / np.arange(1, rows + 1) ** zipf)
        u = rng.random((n, hot)) * cdf[-1]
        cols.append(np.minimum(np.searchsorted(cdf, u, side="right"),
                               rows - 1))
    x = rng.standard_normal((n, num_dense)).astype(np.float32)
    w = rng.standard_normal(num_dense) / np.sqrt(num_dense)
    logit = x @ w + np.log(click_rate / (1.0 - click_rate))
    y = rng.random(n) < 1.0 / (1.0 + np.exp(-logit))
    return (np.concatenate(cols, axis=1).astype(np.int64), x,
            y.astype(np.float32))


def table_layout(table_rows, hot):
    """(first key of each table [T + 1], first key of each member's
    table [M]): the tables' held rows in table order, member m of an
    example a row of the table whose bag it is in."""
    first = np.concatenate([[0], np.cumsum(table_rows)]).astype(np.int64)
    return first, first[np.repeat(np.arange(len(hot)), hot)]


def _set_embeddings(w0, rng, n_feat: int, dim: int, scale: float,
                    state) -> None:
    """Worker `w0` sets every feature row from the host, a slab at a
    time: the embedding uniform in +-`scale`, then `state` in as many
    columns again (a training row's AdaGrad half), or with `state` None
    the embedding alone (a served row)."""
    slab = max(1, (1 << 24) // dim)
    for lo in range(0, n_feat, slab):
        n = min(slab, n_feat - lo)
        emb = (rng.random((n, dim), dtype=np.float32) - 0.5) * (2 * scale)
        if state is not None:
            emb = np.concatenate([emb, np.full_like(emb, state)], axis=1)
        w0.set(np.arange(lo, lo + n), emb)


class CtrRun(AppRun):
    """One training run: the server, its workers and their fused runners,
    the examples with their keys, and the pass count over all train()
    calls."""

    tag = "ctr"

    def __init__(self, args, data):
        self.table_rows = _ints(args.table_rows)
        self.hot = _ints(args.multi_hot_sizes)
        assert len(self.table_rows) == len(self.hot), \
            "one multi-hot size a table"
        self.dim = args.embedding_dim
        bottom, top = _ints(args.bottom_mlp), _ints(args.top_mlp)
        self.layout = DenseLayout(
            dense_tensors(args.dense_features, self.dim, len(self.hot),
                          bottom, top, args.dcn_layers, args.dcn_rank),
            args.dense_row)
        self._loss = make_dlrm_loss(self.layout, self.hot, len(bottom),
                                    args.dcn_layers, len(top))
        # keys: the tables' rows in table order, then the dense rows;
        # member m of an example belongs to table member_table[m]
        self.table_first, self.member_first = table_layout(
            self.table_rows, self.hot)
        self.n_feat = int(self.table_first[-1])
        self.n_dense = self.layout.num_rows
        num_keys = self.n_feat + self.n_dense
        value_lengths = np.empty(num_keys, dtype=np.int64)
        value_lengths[:self.n_feat] = 2 * self.dim
        value_lengths[self.n_feat:] = 2 * args.dense_row
        self.attach_server(args, make_server(
            args, num_keys, value_lengths,
            num_workers=args.num_workers or None))
        kc = self.srv.ab.key_class
        self.c_feat, self.c_dense = int(kc[0]), int(kc[self.n_feat])
        assert self.c_feat != self.c_dense, \
            "feature rows and dense rows need different lengths"
        self.dense_keys = np.arange(self.n_feat, num_keys, dtype=np.int64)
        self.mean_loss = 0.0
        self.set_examples(*data)

    def set_examples(self, members, x, y) -> None:
        """The examples this run trains on: `members` [n, M] table-local
        row ids (M = sum of the multi-hot sizes, bags in table order),
        dense features `x` [n, num_dense], labels `y` [n]. Partitioned
        contiguously over all processes' workers, each worker's share
        cut into its batches; what was prepared of the examples before
        (`_batch`) is dropped."""
        self.members = np.asarray(members, dtype=np.int64)
        assert self.members.shape[1] == len(self.member_first), \
            self.members.shape
        self.x = np.asarray(x, dtype=np.float32)
        self.y = np.asarray(y, dtype=np.float32)
        parts = global_worker_slices(len(self.members), self.num_workers)
        # worker -> the example indices of each of its batches, and the
        # batches prepared so far (None: not yet)
        self._batch_idx = [
            [mine[idx] for idx in wrap_batches(len(mine),
                                               self.args.batch_size)]
            for mine in parts]
        self._plans = [[None] * len(idxs) for idxs in self._batch_idx]

    def runner_spec(self) -> dict:
        return dict(
            loss_fn=self._loss,
            role_class={"feat": self.c_feat, "dense": self.c_dense},
            role_dim={"feat": self.dim, "dense": self.args.dense_row})

    def precompile(self) -> int:
        """`Server.precompile` with this app's sizes: an intent names at
        most M * B feature keys and every dense key; the loop drives one
        kind of runner, a batch of B examples a step. Returns how many
        planner programs ran."""
        a, M = self.args, len(self.member_first)
        B = a.batch_size
        roles = {"feat": np.zeros((M, B), dtype=np.int64),
                 "dense": self.dense_keys}
        put = self.srv.ctx.put_replicated
        aux = (put(np.zeros((B, a.dense_features), np.float32)),
               put(np.zeros(B, np.float32)))
        steps = [(self.device_runner(self.workers[0].shard), roles, aux)]
        return self.srv.precompile(
            {self.c_feat: min(M * B, self.n_feat),
             self.c_dense: self.n_dense}, steps)

    def init_model(self) -> None:
        """Worker 0 sets every row from the host: embeddings uniform in
        +-`--init_scale`, every dense tensor uniform in +-1/sqrt(fan_in),
        the AdaGrad columns at --adagrad_init."""
        a = self.args
        rng = np.random.default_rng(a.seed)
        from ..parallel import control
        w0 = self.workers[0]
        w0.begin_setup()
        if control.process_id() == 0:
            _set_embeddings(w0, rng, self.n_feat, self.dim, a.init_scale,
                            a.adagrad_init)
            r = a.dense_row
            wts = (rng.random((self.n_dense, r), dtype=np.float32) - 0.5) \
                * (2 * self.layout.row_scale()[:, None])
            w0.set(self.dense_keys, np.concatenate(
                [wts, np.full_like(wts, a.adagrad_init)], axis=1))
            w0.wait_all()
        w0.end_setup()

    # -- a pass ----------------------------------------------------------------

    def feat_keys(self, idx: np.ndarray) -> np.ndarray:
        """The feature keys of the examples `idx`, member-major [M, B]."""
        return (self.members[idx] + self.member_first).T.copy()

    def _batch(self, wi: int, bi: int) -> Batch:
        """Batch `bi` of worker `wi`, built at most once for these
        examples: the role keys, their distinct keys, and the upload of
        the dense features and labels, kept on the device (the walk
        adds the keys' upload, which is kept too)."""
        b = self._plans[wi][bi]
        if b is None:
            idx = self._batch_idx[wi][bi]
            put = self.srv.ctx.put_replicated
            roles = {"feat": self.feat_keys(idx), "dense": self.dense_keys}
            keys = np.concatenate([np.unique(roles["feat"]),
                                   self.dense_keys])
            b = self._plans[wi][bi] = Batch(
                roles, (put(self.x[idx]), put(self.y[idx])), keys)
        return b

    def train_pass(self) -> list:
        """One pass over this process's examples, the workers in turns
        (`AppRun.walk`); returns the steps' losses (device scalars)."""
        losses = []
        for wi, w in enumerate(self.workers):
            self.walk(w, len(self._plans[wi]), partial(self._batch, wi),
                      self.args.lr, eps=ADAGRAD_EPS, on_loss=losses.append)
        return losses

    def pass_end(self, losses) -> tuple:
        """The mean of the pass's losses, fetched once."""
        from ..parallel import control
        with self.srv._span("app.loss_fetch", wait=True):
            mean_loss = float(jnp.mean(jnp.stack(losses))) \
                if losses else 0.0
        self.mean_loss = float(control.allreduce(mean_loss, "mean")[0])
        return self.mean_loss, ""


def open_run(args) -> CtrRun:
    """Set-up: data, server, initialized parameters, compiled programs.
    The returned run's server is live; the caller shuts it down
    (`run.srv.shutdown()`), as `run` does."""
    data = generate_synthetic(
        _ints(args.table_rows), _ints(args.multi_hot_sizes),
        args.dense_features, args.examples, args.zipf, args.click_rate,
        args.seed)
    crun = CtrRun(args, data)
    crun.init_model()
    if args.enforce_full_replication:
        enforce_full_replication(crun.workers, crun.n_feat + crun.n_dense)
    crun.precompile()
    return crun


def train(crun: CtrRun) -> float:
    """`--epochs` passes over an opened run, each ended by `quiesce()` and
    the mean of its steps' losses, fetched once; stops at the first pass
    end after `--max_runtime`. Leaves the server up (see open_run) and
    can be called again on the same run. Returns the last pass's mean
    loss."""
    crun.train_passes()
    return crun.mean_loss


def run(args) -> float:
    crun = open_run(args)
    mean_loss = train(crun)
    crun.srv.shutdown()
    return mean_loss


class CtrServe:
    """The serving side of the app: a server of the tables' feature keys
    alone, rows of `--embedding_dim` floats, and (once `open_plane` ran)
    the `ServePlane` its requests go through."""

    def __init__(self, args):
        self.args = args
        self.table_rows = _ints(args.table_rows)
        self.hot = _ints(args.multi_hot_sizes)
        assert len(self.table_rows) == len(self.hot), \
            "one multi-hot size a table"
        self.dim = args.embedding_dim
        self.table_first, self.member_first = table_layout(
            self.table_rows, self.hot)
        self.n_feat = int(self.table_first[-1])
        # a table's members are rows member_at[t]:member_at[t + 1] of a
        # request's [members, S] keys
        self.member_at = np.concatenate([[0], np.cumsum(self.hot)])
        self.srv = make_server(args, self.n_feat, self.dim, num_workers=1)
        self.workers = [self.srv.make_worker(0)]
        self.plane = None

    def init_model(self) -> None:
        """Worker 0 sets every row from the host as `CtrRun.init_model`
        sets the embedding half: uniform in +-`--init_scale`."""
        from ..parallel import control
        w0 = self.workers[0]
        w0.begin_setup()
        if control.process_id() == 0:
            _set_embeddings(w0, np.random.default_rng(self.args.seed),
                            self.n_feat, self.dim, self.args.init_scale,
                            None)
            w0.wait_all()
        w0.end_setup()

    def open_plane(self):
        """The serve plane over the tables, its bag programs (and, with
        `--sys.tier 1`, their cold twins and the tier worker's
        programs) compiled: a coalesced batch is 1..`--sys.serve.max_batch` requests of
        `--serve_samples` min,max samples each, every sample M member
        positions in T bags of ONE length class, so one `_gather_pool`
        program a batch."""
        from ..serve import ServePlane
        lo, hi = _ints(self.args.serve_samples)
        most = hi * self.srv.opts.serve_max_batch
        M, T = len(self.member_first), len(self.hot)
        self.plane = ServePlane(self.srv)
        self.plane.precompile_bags(
            ((M * s, T * s) for s in range(lo, most + 1)),
            cid=int(self.srv.ab.key_class[0]), pooling="sum")
        # --sys.tier 1: the tier worker's programs (one shard has no
        # planner programs to compile)
        self.srv.precompile({})
        return self.plane

    def feat_keys(self, members: np.ndarray) -> np.ndarray:
        """The feature keys of samples `members` [S, M] (table-local
        row ids), member-major [M, S]: a training batch's layout."""
        return (np.asarray(members, dtype=np.int64)
                + self.member_first).T.copy()

    def bag_args(self, keys: np.ndarray):
        """`lookup_bags`' (tables, bags) of a request's feature keys
        `keys` [M, S]: table t's S x m_t member keys sample by sample
        (a bag's members in member order) with offsets 0, m_t, 2 m_t,
        ... The reply is one [S, dim] matrix a table."""
        S, at = keys.shape[1], self.member_at
        tables = [keys[at[t]:at[t + 1]].T.ravel()
                  for t in range(len(self.hot))]
        bags = [np.arange(S + 1, dtype=np.int64) * m for m in self.hot]
        return tables, bags

    def close(self) -> None:
        if self.plane is not None:
            self.plane.close()
        self.srv.shutdown()


def open_serve(args) -> CtrServe:
    """Set-up of the serving side: server, initialized rows, the serve
    plane with its programs compiled. The caller makes one
    `plane.session()` a client thread and closes with `close()`."""
    serve = CtrServe(args)
    serve.init_model()
    serve.open_plane()
    return serve


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--table_rows", default="2000,300,40,3,1",
                        help="rows held of each embedding table, comma-"
                             "separated")
    parser.add_argument("--multi_hot_sizes", default="3,2,1,4,1",
                        help="members of each table's bag an example")
    parser.add_argument("--embedding_dim", type=int, default=16)
    parser.add_argument("--dense_features", type=int, default=13)
    parser.add_argument("--bottom_mlp", default="32,16",
                        help="bottom MLP layer sizes; the last is the "
                             "embedding dim")
    parser.add_argument("--top_mlp", default="64,32,1")
    parser.add_argument("--dcn_layers", type=int, default=3)
    parser.add_argument("--dcn_rank", type=int, default=16)
    parser.add_argument("--dense_row", type=int, default=64,
                        help="weights of one dense-network row (the row "
                             "is twice that: AdaGrad's state beside "
                             "them); differs from the embedding dim")
    parser.add_argument("--examples", type=int, default=2048,
                        help="synthetic examples a pass")
    parser.add_argument("--zipf", type=float, default=1.0,
                        help="exponent of the ids' popularity per table")
    parser.add_argument("--click_rate", type=float, default=0.03)
    parser.add_argument("--lookahead", type=int, default=2,
                        help="intent batches ahead")
    parser.add_argument("--init_scale", type=float, default=0.0625)
    parser.add_argument("--adagrad_init", type=float, default=0.0,
                        help="AdaGrad's accumulators at the start; the "
                             "damping of the first steps is ADAGRAD_EPS")
    parser.add_argument("--serve_samples", default="100,700",
                        help="serving: fewest,most samples of a request "
                             "(open_serve compiles the bag programs a "
                             "batch of them can need)")
    add_common_arguments(parser)
    return parser


def main(argv=None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
