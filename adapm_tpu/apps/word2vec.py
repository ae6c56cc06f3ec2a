"""SGNS word2vec app (reference apps/word2vec.cc).

Two PM keys per word — syn0 (input) = 2w, syn1 (output) = 2w+1
(word2vec.cc:83-105); unigram^0.75 negative table (:125-144); AdaGrad; the
logical clock advances per sentence and a read-ahead pipeline (default 1000
sentences, :561-626) signals `Intent` for future sentences; where the
reference calls PrepareSample/PullSample, the fused step draws the negatives
itself (ops/fused.py: the alias table with a Local-scheme snap).
Pair generation for a future sentence is precomputed with a per-sentence
seeded RNG — the moral equivalent of the reference's PeekableRandom
(:445-491), which pre-draws future window sizes.

Training pairs accumulate into fixed-size batches for the fused
gather -> SGNS loss -> AdaGrad -> scatter-add program (ops/fused.py).

`open_run(args)` sets a run up (corpus, vocabulary, server, compiled
programs), `train(run)` trains `--epochs` passes on it (and can be called
again: the pass count lives on the run), `run(args)` is both and shuts the
server down.

Run: python -m adapm_tpu.apps.word2vec --synthetic ...
"""
from __future__ import annotations

import argparse
import sys
from collections import deque
from typing import List

import numpy as np

from ..io import text as textio
from ..models.sgns import (build_alias_table, sgns_loss, subsample_mask,
                           syn0_key, syn1_key)
from .common import (AppRun, Batch, KeyMapper, add_common_arguments,
                     enforce_full_replication, global_worker_slices,
                     make_server, worker0_init)


def _pairs_for(sent: np.ndarray, sent_idx: int, window: int, seed: int,
               counts=None, total: int = 0, sample_t: float = 0.0):
    """Deterministic pairs for a sentence — identical at intent time and at
    train time (PeekableRandom pattern). Frequent-word subsampling
    (word2vec.cc --sample) is applied before pair generation, also
    deterministically per sentence."""
    rng = np.random.default_rng(seed * 1_000_003 + sent_idx)
    if sample_t > 0 and counts is not None:
        sent = sent[subsample_mask(counts, sent, total, sample_t, rng)]
    return textio.skipgram_pairs(sent, window, rng)


class W2vRun(AppRun):
    """One training run: the server, its workers and their fused runners,
    the vocabulary with its counts, the tokenised sentences, and what
    carries over from pass to pass (the pass count, the last mean
    loss). The app is SENTENCE-clocked (an intent a sentence, a batch
    when `--batch_size` pairs have gathered, a clock tick a sentence),
    so its pass is a buffer loop of its own over `AppRun.window` and not
    the batch walk."""

    tag = "w2v"

    def __init__(self, args, words, counts, sents):
        self.words, self.counts = words, counts
        self.total_words = int(counts.sum())
        self.V, self.d = len(counts), args.dim
        if self.V == 0:
            raise SystemExit("empty vocabulary")
        num_keys = 2 * self.V
        self.kmap = KeyMapper(num_keys, args.enforce_random_keys,
                              seed=args.seed)
        self.attach_server(args, make_server(
            args, num_keys, 2 * self.d,
            num_workers=args.num_workers or None))
        # negatives drawn IN-PROGRAM from the unigram^0.75 alias table
        # over the syn1 physical keys, with a Local-scheme snap that may
        # only land on other syn1 keys, never syn0 (the reference's
        # negative table, word2vec.cc:125-144, as two O(V) HBM arrays);
        # per step the host ships only the center/context key batch
        self._neg_alias = build_alias_table(counts)
        self.mean_loss = 0.0
        # per-worker contiguous sentence partition over all processes'
        # workers (reference :524-531)
        self.sents: List[np.ndarray] = sents
        self.slices = global_worker_slices(len(sents), self.num_workers)
        # --scan_steps K: buffer K materialized batches and train them in
        # ONE lax.scan dispatch (runner.run_scan, same contract as the
        # KGE app: placement frozen per window, negative RNG identical to
        # K sequential steps). Clocks still advance per SENTENCE; a
        # buffered batch waits up to ~K*B/pairs-per-sentence clocks
        # before dispatch, so intent windows are extended by a slack
        # estimated from the corpus (otherwise replicas could expire
        # while a batch sits in the window).
        self.scan_slack = 0
        if self.K > 1:
            probe = [len(self.pairs(si)[0])
                     for si in range(min(50, len(sents)))]
            est_pairs = max(1.0, float(np.mean(probe)) if probe else 1.0)
            self.scan_slack = int(np.ceil(
                self.K * args.batch_size / est_pairs)) * 2 + self.K

        # what a pass is made of
        obs = self.srv.obs
        self._c_sentences = obs.counter("app.sentences_total",
                                        unit="sentences", shared=True)
        self._c_pairs = obs.counter("app.pairs_total", unit="pairs",
                                    shared=True)

    def pairs(self, si: int):
        """(centers, contexts) of sentence `si`, the same at intent time
        and at train time."""
        a = self.args
        return _pairs_for(self.sents[si], si, a.window, a.seed,
                          self.counts, self.total_words, a.sample)

    def runner_spec(self) -> dict:
        a = self.args
        return dict(
            loss_fn=sgns_loss,
            role_class={"center": 0, "ctx": 0, "neg": 0},
            role_dim={k: self.d for k in ("center", "ctx", "neg")},
            neg_role="neg", neg_shape=(a.batch_size, a.negative),
            neg_population=self.kmap(syn1_key(np.arange(self.V))),
            neg_alias=self._neg_alias,
            # every worker's runner compiles its own programs, as ever.
            # Shared, seven compile stalls leave a pass's first steps,
            # the ActionTimer (wall clock) acts on other intents, and
            # the mesh's pinned losses move in the fifth digit
            # (tests/test_word2vec_run.py; with
            # --sys.time_intent_actions 0 both give the same bits)
            programs=None)

    def precompile(self) -> int:
        """`Server.precompile` with this app's sizes: an intent names one
        sentence's keys, at most two a pair and never more than 2B; the
        loop drives one kind of runner, a batch of B pairs a step (a
        --scan_steps window still compiles at its first use). Returns
        how many planner programs ran."""
        B = self.args.batch_size
        z = np.zeros(B, dtype=np.int64)
        steps = [(self.device_runner(self.workers[0].shard),
                  {"center": z, "ctx": z}, None)]
        return self.srv.precompile({0: min(2 * B, 2 * self.V)}, steps)

    def init_model(self) -> None:
        """Worker 0 sets every row from the host: syn0 ~ U[-.5/d, .5/d],
        syn1 = 0 (classic w2v); rows [emb | adagrad]."""
        a, V, d = self.args, self.V, self.d
        rng = np.random.default_rng(a.seed)
        init = np.zeros((2 * V, 2 * d), dtype=np.float32)
        init[syn0_key(np.arange(V)), :d] = \
            (rng.random((V, d)).astype(np.float32) - 0.5) / d
        init[:, d:] = a.adagrad_init
        worker0_init(self.workers, self.kmap(np.arange(2 * V)), init)

    def train_pass(self) -> list:
        """One pass over this process's sentences; returns the steps'
        losses (device scalars; a scan window's are a [K] vector)."""
        args, srv = self.args, self.srv
        B = args.batch_size
        losses = []
        for wi, w in enumerate(self.workers):
            my = self.slices[wi].tolist()
            # (sent position, centers, contexts) of prepared future
            # sentences
            prepared: deque = deque()
            buf_c: List[np.ndarray] = []
            buf_x: List[np.ndarray] = []

            def prepare(pos: int, ahead: int) -> None:
                """Signal intent for the sentence that will be trained
                `ahead` clocks from now."""
                with srv._span("app.prepare", self._h_prepare,
                               work=self._h_prepare_work):
                    c, x = self.pairs(my[pos])
                    self._c_sentences.inc()
                    self._c_pairs.inc(len(c))
                    prepared.append((pos, c, x))
                    if len(c) == 0:
                        return
                    fut = w.current_clock + ahead
                    ks = np.unique(np.concatenate(
                        [self.kmap(syn0_key(c)), self.kmap(syn1_key(x))]))
                    w.intent(ks, fut, fut + 1 + self.scan_slack)

            # prime the pipeline
            for pos in range(min(args.readahead, len(my))):
                prepare(pos, ahead=pos)

            win = self.window(w, args.lr, on_loss=losses.append)

            n_buf = 0
            for pos in range(len(my)):
                if pos + args.readahead < len(my):
                    prepare(pos + args.readahead, ahead=args.readahead)
                _, c, x = prepared.popleft()
                if len(c):
                    buf_c.append(self.kmap(syn0_key(c)))
                    buf_x.append(self.kmap(syn1_key(x)))
                    n_buf += len(c)

                while n_buf >= B:
                    cc = np.concatenate(buf_c)
                    xx = np.concatenate(buf_x)
                    win.add(Batch({"center": cc[:B], "ctx": xx[:B]}, None))
                    buf_c, buf_x = [cc[B:]], [xx[B:]]
                    n_buf -= B
                w.advance_clock()
            win.flush()  # partial window at worker end
            # tail: wrap-pad the remaining pairs into one final batch
            # (a step of its own, no rounds after it)
            if n_buf > 0:
                cc = np.concatenate(buf_c)
                xx = np.concatenate(buf_x)
                reps = -(-B // len(cc))
                losses.append(self.device_runner(w.shard)(
                    {"center": np.tile(cc, reps)[:B],
                     "ctx": np.tile(xx, reps)[:B]}, None, args.lr))
        return losses

    def pass_end(self, losses) -> tuple:
        """The mean of the pass's losses."""
        from ..parallel import control
        with self.srv._span("app.loss_fetch", wait=True):
            # scan windows contribute [K] loss vectors, per-step
            # path scalars
            mean_loss = float(np.mean(np.concatenate(
                [np.ravel(np.asarray(l)) for l in losses]))) \
                if losses else 0.0
        self.mean_loss = float(control.allreduce(mean_loss, "mean")[0])
        return self.mean_loss, ""

    def after_pass(self) -> None:
        from ..parallel import control
        if self.args.export_prefix and control.process_id() == 0:
            _export(self.srv, self.kmap, self.words, self.d,
                    f"{self.args.export_prefix}epoch{self.epoch}.txt")


def _load_corpus(args):
    """(words, counts, sentences as arrays of word ids) of --data, or of
    a synthetic corpus written to --synthetic_path."""
    if args.data:
        corpus = args.data
    else:
        corpus = args.synthetic_path or "/tmp/adapm_w2v_corpus.txt"
        textio.generate_synthetic_corpus(
            corpus, vocab_size=args.synthetic_vocab,
            num_sentences=args.synthetic_sentences, seed=args.seed)
    words, counts, vocab = textio.build_vocab(corpus, args.min_count)
    return words, counts, list(textio.sentences(corpus, vocab))


def open_run(args) -> W2vRun:
    """Set-up: corpus, vocabulary, server, initialized vectors, compiled
    programs. The returned run's server is live; the caller shuts it
    down (`run.srv.shutdown()`), as `run` does."""
    wrun = W2vRun(args, *_load_corpus(args))
    wrun.init_model()
    if args.enforce_full_replication:
        enforce_full_replication(wrun.workers, 2 * wrun.V)
    wrun.precompile()
    return wrun


def train(wrun: W2vRun) -> float:
    """`--epochs` passes over an opened run, each ended by `quiesce()` and
    the mean of its steps' losses; stops at the first pass end after
    `--max_runtime`. Leaves the server up (see open_run) and can be
    called again on the same run. Returns the last pass's mean loss."""
    wrun.train_passes()
    return wrun.mean_loss


def run(args) -> float:
    wrun = open_run(args)
    mean_loss = train(wrun)
    wrun.srv.shutdown()
    return mean_loss


def _export(srv, kmap, words, d, path: str) -> None:
    """Write syn0 embeddings in the classic word2vec text format (the
    reference writes epoch embeddings, word2vec.cc:367-416)."""
    V = len(words)
    flat = srv.read_main(kmap(syn0_key(np.arange(V))))
    emb = flat.reshape(V, 2 * d)[:, :d]
    with open(path, "w") as f:
        f.write(f"{V} {d}\n")
        for w, row in zip(words, emb):
            f.write(w + " " + " ".join(f"{v:.6f}" for v in row) + "\n")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--data", default=None, help="corpus text file")
    parser.add_argument("--synthetic_path", default=None)
    parser.add_argument("--synthetic_vocab", type=int, default=200)
    parser.add_argument("--synthetic_sentences", type=int, default=300)
    parser.add_argument("--dim", type=int, default=32)
    parser.add_argument("--window", type=int, default=5)
    parser.add_argument("--negative", type=int, default=5)
    parser.add_argument("--min_count", type=int, default=1)
    parser.add_argument("--sample", type=float, default=1e-3,
                        help="frequent-word subsampling threshold "
                             "(word2vec.cc --sample; 0 disables)")
    parser.add_argument("--readahead", type=int, default=1000,
                        help="sentences of intent/sample lookahead")
    parser.add_argument("--scan_steps", type=int, default=1,
                        help="batches trained per device dispatch "
                             "(lax.scan window, runner.run_scan; same "
                             "contract as the KGE app's --scan_steps)")
    parser.add_argument("--adagrad_init", type=float, default=1e-6)
    parser.add_argument("--export_prefix", default=None)
    add_common_arguments(parser)
    return parser


def main(argv=None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
