"""The word2vec app held to a window: `open_run` / `train(run)` / `run` =
both (`W2vRun`), at the sizes of `tests/test_apps.py`'s word2vec tests.
The epoch loop is the one `run(args)` had before the split, so its mean
losses are that commit's to the bit (the constants below are what
95f421c's `run(args)` returned here, on the 8-device CPU mesh and on one
shard; the mesh's third epoch was recorded anew in PR 36, whose replica
variant of the fused step is another program: sample-major, replica rows
patched in, one ulp of the float32 mean away from `0x1.18bce0p+1`; the
first two epochs and one shard read as they did)."""
import json

import numpy as np
import pytest

from adapm_tpu.apps import word2vec as w2v

FAST = ["--sys.sync.max_per_sec", "0", "--sys.prefetch", "0"]
# the last epoch's mean loss of `run(args)` with --epochs 1, 2, 3 at the
# parent commit, as float.hex(): the test_word2vec_app sizes on the
# mesh, and on one shard with subsampling
PARENT = {
    (): ["0x1.a1d02a0000000p+1", "0x1.2a70720000000p+1",
         "0x1.18bce20000000p+1"],
    # the mesh with the ActionTimer off (PR 44, read on PR 43's commit):
    # which round acts on an intent then follows no wall clock
    ("--sys.time_intent_actions", "0"): [
        "0x1.a3ef080000000p+1", "0x1.2a8d600000000p+1",
        "0x1.18d5820000000p+1"],
    ("--num_shards", "1", "--sample", "1e-3"): [
        "0x1.4445040000000p+1", "0x1.211aa40000000p+1",
        "0x1.169ae20000000p+1"],
}


def _args(tmp_path, epochs, *extra):
    return w2v.build_parser().parse_args(
        ["--synthetic_vocab", "60", "--synthetic_sentences", "80",
         "--synthetic_path", str(tmp_path / "corpus.txt"),
         "--dim", "8", "--window", "3", "--negative", "3",
         "--epochs", str(epochs), "--batch_size", "128", "--lr", "0.1",
         "--readahead", "20", "--sample", "0"] + FAST + list(extra))


def _table(run):
    return np.asarray(run.srv.read_main(np.arange(2 * run.V))).copy()


@pytest.mark.parametrize("extra", sorted(PARENT))
def test_losses_per_epoch_are_the_parent_s_to_the_bit(tmp_path, extra):
    """One `train(run)` call an epoch on ONE run gives, epoch by epoch,
    what the parent's `run(args)` gave for 1, 2 and 3 epochs."""
    run = w2v.open_run(_args(tmp_path, 1, *extra))
    try:
        got = [float(w2v.train(run)).hex() for _ in range(3)]
    finally:
        run.srv.shutdown()
    assert got == PARENT[extra]


def test_run_is_open_run_plus_train(tmp_path):
    a = w2v.run(_args(tmp_path, 2))
    run = w2v.open_run(_args(tmp_path, 2))
    b = w2v.train(run)
    run.srv.shutdown()
    assert a == b == float.fromhex(PARENT[()][1])


@pytest.mark.parametrize("scan", [1, 3])
def test_two_train_calls_of_one_epoch_equal_one_call_of_two(tmp_path, scan):
    extra = ("--num_shards", "1", "--scan_steps", str(scan))
    one = w2v.open_run(_args(tmp_path, 2, *extra))
    last_one = w2v.train(one)
    two = w2v.open_run(_args(tmp_path, 1, *extra))
    w2v.train(two)
    assert two.epoch == 1
    last_two = w2v.train(two)
    try:
        assert (one.epoch, last_one) == (two.epoch, last_two)
        assert np.array_equal(_table(one), _table(two))
    finally:
        one.srv.shutdown()
        two.srv.shutdown()


def test_max_runtime_stops_at_the_first_epoch_end(tmp_path):
    run = w2v.open_run(_args(tmp_path, 50, "--max_runtime", "1e-9"))
    try:
        w2v.train(run)
        assert run.epoch == 1
        w2v.train(run)
        assert run.epoch == 2
    finally:
        run.srv.shutdown()


def test_open_run_compiles_the_step(tmp_path, monkeypatch):
    """ROADMAP B1.4's last app: `open_run` calls `W2vRun.precompile`, and
    the runner's step has run once (on out-of-bounds coordinates) before
    the first epoch."""
    from adapm_tpu.ops import DeviceRoutedRunner
    seen = []
    precompile = DeviceRoutedRunner.precompile
    monkeypatch.setattr(
        DeviceRoutedRunner, "precompile",
        lambda self, roles, *aux: seen.append(
            {r: np.shape(k) for r, k in roles.items()})
        or precompile(self, roles, *aux))
    run = w2v.open_run(_args(tmp_path, 1, "--num_shards", "1"))
    try:
        assert seen == [{"center": (128,), "ctx": (128,)}]
        before = _table(run)
        assert run.device_runner(run.workers[0].shard).steps == 0
        w2v.train(run)
        assert not np.array_equal(_table(run), before)
    finally:
        run.srv.shutdown()


def test_sentence_counters_and_spans(tmp_path):
    """`app.sentences_total` and `app.pairs_total` count what the epochs
    prepared (every sentence once an epoch, its pairs as `W2vRun.pairs`
    gives them); under --sys.trace.spans the loop's phases are in the
    span trace."""
    run = w2v.open_run(_args(tmp_path, 2, "--num_shards", "1",
                             "--sys.trace.spans", "1",
                             "--sys.stats.out", str(tmp_path)))
    try:
        w2v.train(run)
        obs = run.srv.obs
        n = len(run.sents)
        pairs = sum(len(run.pairs(si)[0]) for si in range(n))
        assert obs.find("app.sentences_total").snap() == 2 * n
        assert obs.find("app.pairs_total").snap() == 2 * pairs
        assert obs.find("app.prepare_s").snap()["count"] == 2 * n
        assert obs.find("app.pass_end_s").snap()["count"] == 2
        # a pass dispatches its pairs in whole batches and a padded tail
        assert run.device_runner(0).steps == 2 * -(-pairs // 128)
        doc = json.load(open(run.srv.write_trace()))
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        for must in ("app.prepare", "app.pass_end", "app.loss_fetch",
                     "kv.quiesce", "kv.intent", "fused.dispatch"):
            assert must in names, must
    finally:
        run.srv.shutdown()
