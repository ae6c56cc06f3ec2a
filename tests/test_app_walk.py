"""The one batch walk (`apps/common.py AppRun.walk`) as the three
batch-clocked apps drive it (KGE, MF columnwise and plain, CTR), on a
server of four kv shards with one worker: what is called, in which order
and with what, recorded at `Worker.intent`, the runner's `__call__` /
`run_scan`, `Server.drive_rounds` and `Worker.advance_clock`. The values
a walk trains are held elsewhere (the apps' reference tests pin their
losses to the bit)."""
import os
import re

import numpy as np
import pytest

from adapm_tpu.apps import ctr, knowledge_graph_embeddings as kge, \
    matrix_factorization as mf
from adapm_tpu.core.kv import Server, Worker
from adapm_tpu.ops import DeviceRoutedRunner

B, N, ROUNDS = 16, 10, 2      # batch size, batches a pass, rounds a step
COMMON = ["--batch_size", str(B), "--epochs", "1", "--num_shards", "4",
          "--num_workers", "1", "--seed", "0", "--sync_rounds_per_step",
          str(ROUNDS), "--sys.sync.max_per_sec", "0"]


def _open_kge(extra):
    run = kge.open_run(kge.build_parser().parse_args(
        ["--dim", "8", "--neg_ratio", "2", "--synthetic_entities", "60",
         "--synthetic_relations", "4", "--synthetic_triples", "400",
         "--eval_every", "0", "--lookahead", "3"] + COMMON + extra))
    run.ds.train = run.ds.train[:N * B]
    return run, kge.train


def _open_mf(algorithm, extra):
    run = mf.open_run(mf.build_parser().parse_args(
        ["--rows", "160", "--cols", "96", "--nnz", str(N * B), "--rank", "8",
         "--algorithm", algorithm, "--lookahead", "2"] + COMMON + extra))
    return run, mf.train


def _open_ctr(extra):
    run = ctr.open_run(ctr.build_parser().parse_args(
        ["--examples", str(N * B), "--lookahead", "2"] + COMMON + extra))
    return run, ctr.train


APPS = {"kge": _open_kge,
        "mf-columnwise": lambda extra: _open_mf("columnwise", extra),
        "mf-plain": lambda extra: _open_mf("plain", extra),
        "ctr": _open_ctr}


def _record(monkeypatch, events):
    """Every call of the walk's five callees from here on is an entry of
    `events`, in call order."""
    def wrap(cls, name, note):
        orig = getattr(cls, name)

        def recorded(self, *a, **kw):
            events.append(note(self, *a, **kw))
            return orig(self, *a, **kw)
        monkeypatch.setattr(cls, name, recorded)

    wrap(Worker, "intent", lambda w, keys, start, end=None: (
        "intent", np.asarray(keys), start, end, w.current_clock))
    wrap(Worker, "advance_clock", lambda w: ("tick",))
    wrap(Server, "drive_rounds", lambda s, n=1: ("rounds", n))
    wrap(Server, "quiesce", lambda s: ("quiesce",))
    wrap(DeviceRoutedRunner, "__call__",
         lambda r, roles, aux, lr, eps=1e-10, staged=None: (
             "step", roles, staged, r.server._workers[0].current_clock))
    wrap(DeviceRoutedRunner, "run_scan",
         lambda r, batches, auxes, lr, eps=1e-10: (
             "scan", list(batches), r.server._workers[0].current_clock))


def _distinct(roles) -> np.ndarray:
    return np.unique(np.concatenate([np.ravel(k) for k in roles.values()]))


def _pass(monkeypatch, app, extra):
    """One `train(run)` pass of `app`, recorded: (events of the walk,
    --lookahead)."""
    run, train = APPS[app](extra)
    events, lookahead = [], run.args.lookahead
    try:
        with monkeypatch.context() as m:
            _record(m, events)
            train(run)
    finally:
        run.srv.shutdown()
    # the walk is what precedes the pass end's `quiesce()`
    return events[:[e[0] for e in events].index("quiesce")], lookahead


@pytest.mark.parametrize("prefetch", ["0", "1"])
@pytest.mark.parametrize("app", sorted(APPS))
def test_a_pass_step_by_step(monkeypatch, app, prefetch):
    """Per step (K = 1): (a) batch `bi`'s intent is made min(--lookahead,
    bi) clocks before its step, for the step's clock alone, and names
    exactly the distinct keys of its roles; (b) the dispatch is handed
    the keys' upload, made where the batch was prepared, with the
    prefetch pipeline and without it; (c) a step is followed by
    --sync_rounds_per_step rounds and one tick."""
    events, lookahead = _pass(monkeypatch, app, ["--sys.prefetch", prefetch])
    intents = [e for e in events if e[0] == "intent"]
    steps = [e for e in events if e[0] == "step"]
    assert len(intents) == len(steps) == N
    assert not [e for e in events if e[0] == "scan"]
    c0 = steps[0][3]
    for bi, ((_, keys, start, end, at), (_, roles, staged, clock)) in \
            enumerate(zip(intents, steps)):
        assert clock == c0 + bi
        assert (start, end) == (clock, clock + 1), (bi, start, end, clock)
        assert start - at == min(lookahead, bi), (bi, start, at)
        assert np.array_equal(np.unique(keys), _distinct(roles)), bi
        assert staged is not None and staged.matches(roles), bi
    # the first --lookahead intents stand before the first turn, every
    # other one at the head of the turn --lookahead ahead of its own
    kinds = [e[0] for e in events]
    assert kinds[:lookahead + 2] == ["intent"] * (lookahead + 1) + ["step"]
    turn = [(e[0], e[1]) if e[0] == "rounds" else (e[0],)
            for e in events if e[0] != "intent"]
    assert turn == [("step",), ("rounds", ROUNDS), ("tick",)] * N


@pytest.mark.parametrize("app", ["kge", "mf-columnwise", "mf-plain"])
def test_a_pass_in_windows_of_four(monkeypatch, app):
    """(d) --scan_steps 4 over 10 batches: two windows of one dispatch
    each, dispatched when their fourth batch is added (three ticks
    after their first) and followed by 4 x the rounds of a step, then
    two single steps at the end of the turn (after their ticks, each
    followed by its rounds); an intent runs a window ahead where
    --lookahead is less, and ends three clocks later than a single
    step's, which covers both delays."""
    events, lookahead = _pass(monkeypatch, app, [
        "--scan_steps", "4", "--sys.prefetch", "0"])
    intents = [e for e in events if e[0] == "intent"]
    assert len(intents) == N
    look = max(lookahead, 4)
    c0 = intents[0][4]
    for bi, (_, keys, start, end, at) in enumerate(intents):
        assert (start, end) == (c0 + bi, c0 + bi + 1 + 3)
        assert start - at == min(look, bi)
    turn = [(e[0], e[1]) if e[0] == "rounds" else (e[0],)
            for e in events if e[0] != "intent"]
    window = [("tick",)] * 3 + [("scan",), ("rounds", 4 * ROUNDS),
                                ("tick",)]
    assert turn == window * 2 + [("tick",)] * 2 + \
        [("step",), ("rounds", ROUNDS)] * 2
    scans = [e for e in events if e[0] == "scan"]
    assert [len(e[1]) for e in scans] == [4, 4]
    assert [e[2] for e in scans] == [c0 + 3, c0 + 7]
    # a window's batches are the intents' in order
    for w, (_, batches, _) in enumerate(scans):
        for j, roles in enumerate(batches):
            assert np.array_equal(np.unique(intents[4 * w + j][1]),
                                  _distinct(roles))
    # the tail's single steps upload their keys in the dispatch, and run
    # inside their intents' clocks
    tail = [e for e in events if e[0] == "step"]
    assert [e[2] for e in tail] == [None, None]
    assert all(i[2] <= e[3] <= i[3] for i, e in zip(intents[8:], tail))


def test_the_apps_do_not_ask_whether_the_pipeline_is_on():
    """The pin: the staging rule (`AppRun.walk`: a batch dispatched as a
    single step has its keys uploaded where it is prepared) reads no
    `srv.prefetch`, and no app does."""
    apps = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "adapm_tpu", "apps")
    for name in sorted(os.listdir(apps)):
        if name.endswith(".py"):
            text = open(os.path.join(apps, name)).read()
            assert not re.search(r"srv\.prefetch|\.prefetch is", text), name
