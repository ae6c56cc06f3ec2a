"""The one span bracket (ISSUE 24): `Server._span(name, hist)`.

  - every phase of a train step and of a served lookup leaves an
    `adapm.*` event on the host plane of a `jax.profiler` trace (the
    profiler's clock: the same file as the device's operations), none
    longer than the loop around it;
  - the seven `serve.*_s` phases are consecutive stamps of one
    request, so they add up to `serve.lookup_s` exactly, per delivered
    request only (shed requests observe nothing), on the locked path,
    the replica fast path and the bag path;
  - the train-step histograms count one observation per step by
    default and none under `--sys.metrics 0`;
  - the compiled step and the store's programs carry their stable
    `jax.named_scope` names;
  - (ISSUE 35) a span marked `wait` adds its seconds to its thread's
    tally and a span given a `work` histogram observes its seconds
    less the tally's growth, so whole = work + the waits beneath it,
    exactly; a span with neither touches nothing; `fused.enqueue_s`
    and `fused.inflight_steps` count one observation a step or scan
    dispatch.
"""
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import adapm_tpu
from adapm_tpu import Server, SystemOptions, make_mesh
from adapm_tpu.ops import DeviceRoutedRunner, fused
from adapm_tpu.serve import DeadlineExceededError, ServePlane

NK, VL = 96, 8

# PERF.md section 3: the phases of a train step ...
TRAIN_SPANS = ("kv.intent", "fused.dispatch", "fused.key_upload",
               "fused.rng_refill", "fused.locstat_drain",
               "kv.drive_rounds", "kv.advance_clock", "kv.quiesce",
               "app.prepare", "app.pass_end", "app.loss_fetch",
               "app.loss_allreduce", "sync.round",
               "fused.enqueue", "fused.route_upload", "fused.route_patch",
               "store.enqueue", "kv.block")
# the wait spans (one blocking call each) and the phases each nests in
# (the pass end's flush calls the sync programs outside a round)
WAIT_NESTS_IN = {"fused.enqueue": ("fused.dispatch",),
                 "fused.key_upload": ("fused.dispatch", "app.prepare"),
                 "fused.route_upload": ("fused.route_refresh",),
                 "fused.route_patch": ("fused.route_refresh",),
                 "store.enqueue": ("sync.round", "kv.quiesce"),
                 "kv.block": ("kv.quiesce",)}
# every span given a work histogram: (whole, work)
WORK_HISTS = (("kv.intent_s", "kv.intent_work_s"),
              ("fused.dispatch_s", "fused.dispatch_work_s"),
              ("kv.drive_rounds_s", "kv.drive_rounds_work_s"),
              ("kv.advance_clock_s", "kv.advance_clock_work_s"),
              ("fused.route_refresh_s", "fused.route_refresh_work_s"),
              ("sync.round_s", "sync.round_work_s"),
              ("kv.sync_replicas_s", "kv.sync_replicas_work_s"),
              ("kv.relocate_s", "kv.relocate_work_s"))
# ... and of a served lookup (queue crosses threads: no span)
SERVE_SPANS = ("serve.admit", "serve.wait", "serve.take",
               "serve.dispatch", "serve.copy_out",
               "serve.deliver")
PHASES = ("admit", "queue", "batch_wait", "dispatch", "copy_out",
          "deliver", "wake")
STEP_HISTS = ("kv.intent_s", "fused.dispatch_s", "fused.key_upload_s",
              "kv.drive_rounds_s", "kv.advance_clock_s")


@pytest.fixture(scope="module")
def ctx():
    return make_mesh(8)


def _loss(embs, aux):
    return ((embs["a"] * embs["b"]).sum(-1)[:, None]
            * embs["neg"].sum(-1)).mean() ** 2


def _server(ctx, **opts):
    s = Server(NK, VL, ctx=ctx,
               opts=SystemOptions(sync_max_per_sec=0, prefetch=False,
                                  cache_slots_per_shard=8, **opts))
    w = s.make_worker(0)
    vals = np.arange(NK * VL, dtype=np.float32).reshape(NK, VL) / 100 + 1
    w.wait(w.set(np.arange(NK), vals))
    return s, w


def _runner(s, w, score_fn=None):
    return DeviceRoutedRunner(
        s, _loss, role_class={"a": 0, "b": 0, "neg": 0},
        role_dim={"a": VL // 2, "b": VL // 2, "neg": VL // 2},
        shard=w.shard, neg_role="neg", neg_shape=(8, 2), seed=3,
        score_fn=score_fn)


def _step(s, w, runner, rng):
    """The apps' per-step body."""
    b = {"a": rng.integers(0, NK, 8), "b": rng.integers(0, NK, 8)}
    w.intent(np.unique(np.concatenate([b["a"], b["b"]])),
             w.current_clock + 1, w.current_clock + 2)
    loss = runner(b, None, 0.05)
    s.drive_rounds(1)
    w.advance_clock()
    return loss


def _hist(s, name):
    h = s.obs.find(name)
    assert h is not None, name
    return h.snap()


# ---------------------------------------------------------------------------
# (a) every phase is an event on the profiler's clock
# ---------------------------------------------------------------------------


def _trace(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)


def _host_events(tmp_path):
    """(thread line, name, start ns, duration ns) of the newest trace's
    `adapm.*` and `test.loop` host events."""
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    out = []
    for plane_ in ProfileData.from_file(path).planes:
        if plane_.name != "/host:CPU":
            continue
        for li, line in enumerate(plane_.lines):
            for e in line.events:
                if e.name.startswith("adapm.") or e.name == "test.loop":
                    out.append((li, e.name, float(e.start_ns),
                                float(e.duration_ns)))
    return out


def test_spans_on_the_profilers_clock(ctx, tmp_path):
    from adapm_tpu.apps import knowledge_graph_embeddings as kge
    s, w = _server(ctx)
    runner = _runner(s, w)
    rng = np.random.default_rng(0)
    args = kge.build_parser().parse_args(
        ["--dim", "8", "--neg_ratio", "2", "--synthetic_entities", "60",
         "--synthetic_relations", "4", "--synthetic_triples", "96",
         "--epochs", "1", "--batch_size", "32", "--eval_every", "0",
         "--sys.sync.max_per_sec", "0", "--sys.prefetch", "0"])
    run = kge.open_run(args)
    plane = ServePlane(s)
    sess = plane.session()
    _trace(tmp_path)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("test.loop"):
            for _ in range(3):          # the first refills the RNG pool
                jax.block_until_ready(_step(s, w, runner, rng))
            runner.locality_counts()    # drains the device accumulator
            s.quiesce()
            kge.train(run)
            for _ in range(3):
                sess.lookup(rng.integers(0, NK, 5))
        loop_ns = (time.perf_counter() - t0) * 1e9
    finally:
        jax.profiler.stop_trace()
        plane.close()
        run.srv.shutdown()
        s.shutdown()
    longest = {}
    for _, name, _, dur in _host_events(tmp_path):
        longest[name] = max(longest.get(name, 0.0), dur)
    assert "test.loop" in longest
    for name in TRAIN_SPANS + SERVE_SPANS:
        assert "adapm." + name in longest, (name, sorted(longest))
    # no program span encloses the loop around it (PERF.md section 3:
    # the outermost span on a thread is ONE phase)
    for name, ns in longest.items():
        if name != "test.loop":
            assert ns < min(loop_ns, longest["test.loop"]), (name, ns)


# ---------------------------------------------------------------------------
# (b) closure: the seven phases add up to the lookup, delivered only
# ---------------------------------------------------------------------------


def _phase_sums(s):
    return ({p: _hist(s, f"serve.{p}_s")["sum"] for p in PHASES},
            _hist(s, "serve.lookup_s")["sum"])


def _assert_counts(s, n):
    for p in PHASES + ("lookup",):
        assert _hist(s, f"serve.{p}_s")["count"] == n, p


def test_serve_phases_close_per_request(ctx):
    """One request at a time: each lookup adds one observation to every
    histogram, and the seven phase observations sum to its lookup_s."""
    s, w = _server(ctx)
    rng = np.random.default_rng(1)
    with ServePlane(s) as plane:
        sess = plane.session()
        for i in range(1, 6):
            before, look0 = _phase_sums(s)
            keys = rng.integers(0, NK, 7)
            assert np.array_equal(sess.lookup(keys), w.pull_sync(keys))
            after, look1 = _phase_sums(s)
            parts = [after[p] - before[p] for p in PHASES]
            assert all(x >= 0.0 for x in parts), parts
            assert sum(parts) == pytest.approx(look1 - look0, abs=1e-9)
            _assert_counts(s, i)
    s.shutdown()


def test_serve_phases_close_under_load_shed_excluded(ctx):
    """Concurrent clients, micro-batches of several requests, and a shed
    request: the counts equal the DELIVERED count and the sums close."""
    s, w = _server(ctx)
    plane = ServePlane(s, start=False)  # paused: nothing serves yet
    with pytest.raises(DeadlineExceededError):
        plane.session().lookup(np.array([1]), deadline_ms=20)
    _assert_counts(s, 0)                # a shed request observes nothing
    plane.batcher.start()
    n_clients, n_each = 4, 10

    def client(ci):
        sess = plane.session()
        r = np.random.default_rng(ci)
        for _ in range(n_each):
            sess.lookup(r.integers(0, NK, 6))

    ts = [threading.Thread(target=client, args=(ci,))
          for ci in range(n_clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
        assert not t.is_alive()
    _assert_counts(s, n_clients * n_each)
    parts, look = _phase_sums(s)
    assert sum(parts.values()) == pytest.approx(look, abs=1e-7)
    depth = _hist(s, "serve.claim_depth")
    assert depth["count"] == n_clients * n_each     # one per claim
    plane.close()
    s.shutdown()


# ---------------------------------------------------------------------------
# (f) the replica fast path and a bag lookup observe all eight
# ---------------------------------------------------------------------------


def test_replica_fast_path_observes_every_phase(ctx):
    # a long refresh interval: the snapshot moves only at refresh_now()
    s, w = _server(ctx, serve_replica_rows=NK,
                   serve_replica_refresh_ms=10_000.0)
    hot = np.arange(16)
    with ServePlane(s) as plane:
        sess = plane.session()
        sess.lookup(hot)
        plane.replica.refresh_now()
        before, look0 = _phase_sums(s)
        hits0 = s.obs.find("serve.replica_hits_total").value
        assert np.array_equal(sess.lookup(hot), w.pull_sync(hot))
        assert s.obs.find("serve.replica_hits_total").value == hits0 + 1
        after, look1 = _phase_sums(s)
        _assert_counts(s, 2)
        # lock-free hit: no dispatch, no copy from the device
        for p in ("dispatch", "copy_out"):
            assert after[p] == before[p], p
        assert sum(after[p] - before[p] for p in PHASES) == \
            pytest.approx(look1 - look0, abs=1e-9)
    s.shutdown()


@pytest.mark.parametrize("fused_bags", [True, False],
                         ids=["fused", "hostpool"])
def test_bag_lookup_observes_every_phase(ctx, fused_bags):
    s, w = _server(ctx, serve_bags=fused_bags)
    members = np.arange(12)
    with ServePlane(s) as plane:
        sess = plane.session()
        out = sess.lookup_bags([members], [np.arange(0, 13, 4)])
        assert out[0].shape == (3, VL)
        _assert_counts(s, 1)
        parts, look = _phase_sums(s)
        assert sum(parts.values()) == pytest.approx(look, abs=1e-9)
    s.shutdown()


# ---------------------------------------------------------------------------
# (c) one observation per step by default; none under --sys.metrics 0
# ---------------------------------------------------------------------------


def test_step_histograms_count_one_per_step(ctx):
    s, w = _server(ctx)
    runner = _runner(s, w)
    rng = np.random.default_rng(2)
    n = 5
    for _ in range(n):
        _step(s, w, runner, rng)
    s.quiesce()
    for name in STEP_HISTS:
        assert _hist(s, name)["count"] == n, name
    assert _hist(s, "kv.quiesce_s")["count"] == 1
    # the planner round inside drive_rounds: bracketed once, not twice
    assert _hist(s, "sync.round_s")["count"] == \
        s.sync.stats.rounds
    s.shutdown()


def test_metrics_off_registers_nothing(ctx):
    s, w = _server(ctx, metrics=False)
    runner = _runner(s, w)
    rng = np.random.default_rng(2)
    _step(s, w, runner, rng)
    s.quiesce()
    with ServePlane(s) as plane:
        plane.session().lookup(np.arange(4))
    assert s.obs.names() == []
    s.shutdown()


def test_span_tracer_still_records_under_flag(ctx, tmp_path):
    """--sys.trace.spans keeps its Chrome JSON: the bracket records
    into the SpanTracer as before, new names included."""
    s, w = _server(ctx, trace_spans=True, stats_out=str(tmp_path))
    runner = _runner(s, w)
    _step(s, w, runner, np.random.default_rng(0))
    s.quiesce()
    import json
    doc = json.load(open(s.write_trace()))
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"kv.intent", "fused.dispatch", "kv.drive_rounds",
            "sync.round", "kv.quiesce"} <= names
    s.shutdown()


# ---------------------------------------------------------------------------
# (d) the compiled programs carry their scope names
# ---------------------------------------------------------------------------


def _lowered_device_step(ctx):
    """The lowered text of the device-routed step as the runner calls
    it (arguments taken at its compiled boundary)."""
    s, w = _server(ctx)
    runner = _runner(s, w)
    texts = []
    for name in ("step_fn", "_step_fn_norep"):
        fn = getattr(runner, name)

        def lowering(*args, _fn=fn):
            texts.append(_fn.lower(*args).as_text(debug_info=True))
            return _fn(*args)
        setattr(runner, name, lowering)
    _step(s, w, runner, np.random.default_rng(0))
    s.shutdown()
    assert len(texts) == 1
    return texts[0]


def _lowered_port_programs():
    from adapm_tpu.device import jaxport as jp
    pool = jnp.ones((2, 8, VL))
    idx = jnp.zeros(4, jnp.int32)
    msk = jnp.zeros(4, bool)
    vals = jnp.ones((4, VL))
    thr = jnp.float32(0.0)
    lowered = [
        jp._gather.lower(pool, pool, pool, idx, idx, idx, idx, msk),
        jp._gather_pool.lower(pool, pool, pool, idx, idx, idx, idx, msk,
                              idx, nbags=2, pooling="sum"),
        jp._scatter_add.lower(pool, pool, idx, idx, idx, idx, vals),
        jp._sync_replicas.lower(pool, pool, pool, idx, idx, idx, idx),
        jp._sync_replicas_thresholded.lower(pool, pool, pool, idx, idx,
                                            idx, idx, thr),
        jp._sync_replicas_compressed.lower(pool, pool, pool, idx, idx,
                                           idx, idx, thr, mode="fp16"),
        jp._relocate.lower(pool, pool, idx, idx, idx, idx, idx, idx)]
    return [lo.as_text(debug_info=True) for lo in lowered]


def test_device_routed_step_carries_scope_names(ctx):
    text = _lowered_device_step(ctx)
    for scope in ("adapm_route", "adapm_sampler", "adapm_gather",
                  "adapm_loss_grad", "adapm_adagrad",
                  "adapm_scatter_add"):
        assert scope in text, scope


def test_port_programs_carry_scope_names():
    texts = _lowered_port_programs()
    for text, scope in zip(texts, (
            "adapm_gather", "adapm_gather_pool", "adapm_scatter_add",
            "adapm_sync_replicas", "adapm_sync_replicas",
            "adapm_sync_replicas", "adapm_relocate")):
        assert scope in text, scope


def test_one_bracket_one_annotation_site():
    """`TraceAnnotation` appears under adapm_tpu/ in one file: the
    bracket's implementation."""
    root = os.path.dirname(adapm_tpu.__file__)
    hits = []
    for d, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                p = os.path.join(d, f)
                if "TraceAnnotation" in open(p).read():
                    hits.append(os.path.relpath(p, root))
    assert hits == [os.path.join("obs", "spans.py")], hits


# ---------------------------------------------------------------------------
# (g) waits and self time (ISSUE 35)
# ---------------------------------------------------------------------------


def _pair(s, whole, work):
    """(whole.sum - work.sum, count of both) of one work-histogram pair."""
    a, b = _hist(s, whole), _hist(s, work)
    assert a["count"] == b["count"], (whole, a["count"], b["count"])
    return a["sum"] - b["sum"], a["count"]


def test_work_is_the_whole_less_the_waits_beneath_at_any_depth():
    """A wait nested two spans deep is subtracted from both ancestors; a
    wait on ANOTHER thread from neither."""
    from adapm_tpu.obs.metrics import MetricsRegistry
    from adapm_tpu.obs.spans import Span
    reg = MetricsRegistry()
    h = {n: reg.histogram(n) for n in
         ("outer_s", "outer_work_s", "mid_s", "mid_work_s", "wait_s",
          "other_wait_s")}
    go, done = threading.Event(), threading.Event()

    def other():
        go.wait(10)
        with Span("t.other_wait", h["other_wait_s"], wait=True):
            time.sleep(0.005)       # a stub wait, on its own thread
        done.set()

    t = threading.Thread(target=other)
    t.start()
    for _ in range(3):
        with Span("t.outer", h["outer_s"], work=h["outer_work_s"]):
            with Span("t.mid", h["mid_s"], work=h["mid_work_s"]):
                go.set()
                with Span("t.wait", h["wait_s"], wait=True):
                    time.sleep(0.005)
                done.wait(10)       # the other thread's wait ends here
            with Span("t.wait", h["wait_s"], wait=True):
                time.sleep(0.002)   # a second wait, one span deep
    t.join(10)
    snap = {n: m.snap() for n, m in h.items()}
    waited = snap["wait_s"]["sum"]
    assert snap["wait_s"]["count"] == 6 and waited >= 3 * 0.007
    assert snap["other_wait_s"]["sum"] >= 0.005
    # outer holds all six waits, mid the three nested in it
    assert snap["outer_s"]["sum"] - snap["outer_work_s"]["sum"] == \
        pytest.approx(waited, abs=1e-9)
    mid_waits = snap["mid_s"]["sum"] - snap["mid_work_s"]["sum"]
    assert 3 * 0.005 <= mid_waits < waited - 3 * 0.002 + 1e-9
    # the other thread's 5 ms lie inside mid's interval and inside its
    # WORK: they were nobody's wait on this thread
    assert snap["mid_work_s"]["sum"] > 0.0
    assert snap["outer_work_s"]["sum"] >= snap["mid_work_s"]["sum"]


def test_train_step_work_and_waits_close_on_the_whole(ctx):
    """On a live server (rounds inline, one thread): every work
    histogram counts with its whole and never exceeds it; the
    dispatch's waits are its enqueue, key upload, route uploads and
    the calls of the routes' patch program; the
    planner round's are the stores' program calls, subtracted from
    `kv.drive_rounds` above it too."""
    s, w = _server(ctx)
    runner = _runner(s, w)
    rng = np.random.default_rng(5)
    for _ in range(6):
        _step(s, w, runner, rng)
    for whole, work in WORK_HISTS:
        waits, n = _pair(s, whole, work)
        assert n > 0 and waits >= -1e-12, (whole, n, waits)
    disp, n = _pair(s, "fused.dispatch_s", "fused.dispatch_work_s")
    assert n == 6
    beneath = sum(_hist(s, name)["sum"] for name in
                  ("fused.enqueue_s", "fused.key_upload_s",
                   "fused.route_upload_s", "fused.route_patch_s"))
    assert disp == pytest.approx(beneath, abs=1e-9) and beneath > 0.0
    # a refresh's waits are its uploads and its patch program's calls
    # (all of them ran in a dispatch)
    refresh, _ = _pair(s, "fused.route_refresh_s",
                       "fused.route_refresh_work_s")
    assert _hist(s, "fused.route_patch_s")["count"] > 0
    assert refresh == pytest.approx(
        _hist(s, "fused.route_upload_s")["sum"]
        + _hist(s, "fused.route_patch_s")["sum"], abs=1e-9)
    # the three counters of the patch path: calls of the program (one a
    # wait span, and ONE a refresh of the mirrors that patched), the
    # refreshes that patched (mirrors or a local index), their entries
    calls = s.obs.find("fused.route_patch_calls_total").snap()
    assert calls == _hist(s, "fused.route_patch_s")["count"]
    assert calls <= s.obs.find("fused.route_patch_total").snap() \
        <= s.obs.find("fused.route_refresh_total").snap()
    assert s.obs.find("fused.route_patch_keys_total").snap() >= calls
    # the planner moved rows (8 shards, fresh intents every step): its
    # program calls are the round's waits, two and three spans deep
    stores = _hist(s, "kv.store_enqueue_s")
    assert stores["count"] > 0
    for whole, work in (("sync.round_s", "sync.round_work_s"),
                        ("kv.drive_rounds_s", "kv.drive_rounds_work_s")):
        assert _pair(s, whole, work)[0] == \
            pytest.approx(stores["sum"], abs=1e-9), whole
    parts = _pair(s, "kv.relocate_s", "kv.relocate_work_s")[0] + \
        _pair(s, "kv.sync_replicas_s", "kv.sync_replicas_work_s")[0]
    assert parts <= stores["sum"] + 1e-9   # + replica_create's calls
    # no wait beneath these two: work is the whole
    for whole, work in (("kv.intent_s", "kv.intent_work_s"),
                        ("kv.advance_clock_s", "kv.advance_clock_work_s")):
        assert _pair(s, whole, work)[0] == pytest.approx(0.0, abs=1e-12)
    s.shutdown()


def _record_tally_writes(monkeypatch):
    """Swap the wait tally for one that lists the thread of every write
    to it (reads are free: only `work` spans read)."""
    from adapm_tpu.obs import spans
    touched = []

    class Recording(threading.local):
        s = 0.0

        def __setattr__(self, k, v):
            touched.append(threading.current_thread().name)
            super().__setattr__(k, v)

    monkeypatch.setattr(spans, "_WAITED", Recording())
    return touched


def test_spans_with_neither_wait_nor_work_never_touch_the_tally(
        ctx, monkeypatch):
    """The serve path's brackets cost what they cost before: no thread
    that serves a lookup writes the wait tally; a train step does."""
    s, w = _server(ctx)
    touched = _record_tally_writes(monkeypatch)
    with ServePlane(s) as plane:
        sess = plane.session()
        for _ in range(4):
            sess.lookup(np.arange(6))
        assert _hist(s, "serve.lookup_s")["count"] == 4
        assert touched == []
    _step(s, w, _runner(s, w), np.random.default_rng(0))
    assert touched
    s.shutdown()


def test_enqueue_and_in_flight_count_one_per_step_and_scan_dispatch(ctx):
    s, w = _server(ctx)
    runner = _runner(s, w, score_fn=lambda embs, aux:
                     (embs["a"] * embs["b"]).sum())
    rng = np.random.default_rng(7)
    batch = lambda: {"a": rng.integers(0, NK, 8),  # noqa: E731
                     "b": rng.integers(0, NK, 8)}
    for _ in range(3):
        _step(s, w, runner, rng)
    runner.run_scan([batch(), batch()], None, 0.05)
    float(runner.score(batch(), None))
    for name in ("fused.enqueue_s", "fused.inflight_steps"):
        # one per step and per scan dispatch, none per score dispatch
        assert _hist(s, name)["count"] == 4 == \
            _hist(s, "fused.dispatch_s")["count"], name
    assert _hist(s, "fused.inflight_steps")["bounds"][:3] == [0, 1, 2]
    s.shutdown()


def test_in_flight_reads_zero_after_block_and_counts_unfinished_steps(ctx):
    s, w = _server(ctx)
    runner = _runner(s, w)
    rng = np.random.default_rng(8)
    for _ in range(3):
        _step(s, w, runner, rng)
    s.block()
    before = _hist(s, "fused.inflight_steps")
    _step(s, w, runner, rng)
    after = _hist(s, "fused.inflight_steps")
    # the first dispatch after block(): every earlier step is done
    assert after["count"] == before["count"] + 1
    assert after["sum"] == before["sum"]
    assert after["buckets"][0] == before["buckets"][0] + 1
    s.block()

    class NeverReady:
        def is_ready(self):
            return False

    s._steps_in_flight.clear()
    runner._note_in_flight = \
        lambda loss: s._steps_in_flight.append(NeverReady())
    n = 5
    for _ in range(n):
        _step(s, w, runner, rng)
    last = _hist(s, "fused.inflight_steps")
    # each dispatch read the number of dispatches before it: 0 .. n-1
    assert last["count"] == after["count"] + n
    assert last["sum"] - after["sum"] == n * (n - 1) / 2
    assert last["max"] == n - 1 and len(s._steps_in_flight) == n
    s._steps_in_flight.clear()
    s.shutdown()


def test_metrics_off_keeps_no_wait_tally_and_no_queue(ctx, monkeypatch):
    s, w = _server(ctx, metrics=False)
    runner = _runner(s, w)
    assert s._steps_in_flight is None and runner._inflight is None
    bracket = s._span("fused.enqueue", wait=True, work=object())
    assert not bracket._wait and bracket._work is None
    touched = _record_tally_writes(monkeypatch)
    _step(s, w, runner, np.random.default_rng(2))
    s.quiesce()
    assert touched == [] and s.obs.names() == []
    s.shutdown()


def test_wait_spans_nest_in_their_phase_on_the_same_thread(ctx, tmp_path):
    """Each wait span is an event INSIDE its phase's event, on the same
    host thread of the profiler's trace."""
    s, w = _server(ctx)
    runner = _runner(s, w)
    rng = np.random.default_rng(9)
    _trace(tmp_path)
    try:
        for _ in range(4):
            jax.block_until_ready(_step(s, w, runner, rng))
        s.quiesce()
    finally:
        jax.profiler.stop_trace()
        s.shutdown()
    events = _host_events(tmp_path)
    for inner, phases in WAIT_NESTS_IN.items():
        inners = [e for e in events if e[1] == "adapm." + inner]
        outers = [e for e in events
                  if e[1] in ["adapm." + p for p in phases]]
        assert inners and outers, (inner, phases)
        for i in inners:
            assert any(o[0] == i[0] and o[2] <= i[2]
                       and i[2] + i[3] <= o[2] + o[3] for o in outers), \
                (i, phases)


def test_brackets_cost_under_twenty_microseconds_a_step(capsys):
    """What ISSUE 35 adds to a dispatched step on the host, with no
    profiler session and the registry on: the step's spans as they are
    now against the same spans as the parent had them (no wait, no work,
    no `fused.enqueue`, no in-flight queue). The best of five rounds of
    2,000 steps, so that a busy sandbox does not fail it; printed."""
    import collections
    from adapm_tpu.obs.metrics import MetricsRegistry
    from adapm_tpu.obs.spans import Span
    reg = MetricsRegistry()
    h = {n: reg.histogram(n) for n in (
        "prepare", "prepare_w", "intent", "intent_w", "dispatch",
        "dispatch_w", "upload", "enqueue", "drive", "drive_w", "round",
        "round_w", "clock", "clock_w")}
    depth = reg.histogram("inflight", unit="steps",
                          bounds=(0, 1, 2, 4, 8, 16, 32, 64, 128))

    class Ready:
        def is_ready(self):
            return True

    dq, ready = collections.deque(), Ready()

    def step_now():
        with Span("app.prepare", h["prepare"], work=h["prepare_w"]):
            with Span("kv.intent", h["intent"], work=h["intent_w"]):
                pass
        with Span("fused.dispatch", h["dispatch"], work=h["dispatch_w"]):
            with Span("fused.key_upload", h["upload"], wait=True):
                pass
            while dq and dq[0].is_ready():
                dq.popleft()
            depth.observe(len(dq))
            with Span("fused.enqueue", h["enqueue"], wait=True):
                pass
            dq.append(ready)
        with Span("kv.drive_rounds", h["drive"], work=h["drive_w"]):
            with Span("sync.round", h["round"], work=h["round_w"]):
                with Span("sync.drain_intents"):
                    pass
        with Span("kv.advance_clock", h["clock"], work=h["clock_w"]):
            pass

    def step_parent():
        with Span("app.prepare", h["prepare"]):
            with Span("kv.intent", h["intent"]):
                pass
        with Span("fused.dispatch", h["dispatch"]):
            with Span("fused.key_upload", h["upload"]):
                pass
        with Span("kv.drive_rounds", h["drive"]):
            with Span("sync.round", h["round"]):
                with Span("sync.drain_intents"):
                    pass
        with Span("kv.advance_clock", h["clock"]):
            pass

    def best(fn, rounds=5, n=2000):
        out = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            out.append((time.perf_counter() - t0) / n * 1e6)
        return min(out)

    parent, now = best(step_parent), best(step_now)
    with capsys.disabled():
        print(f"\nbrackets a dispatched step, us (CPU, no profiler "
              f"session): parent's {parent:.2f}, now {now:.2f}, added "
              f"{now - parent:.2f}")
    # 20 us on this sandbox's CPU, where the parent's brackets read 12.5;
    # in proportion on a slower or busier machine
    assert now - parent < max(20.0, 1.6 * parent), (parent, now)
