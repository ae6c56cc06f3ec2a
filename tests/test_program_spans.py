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
    `jax.named_scope` names.
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
               "app.loss_allreduce", "sync.round")
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


def _runner(s, w):
    return DeviceRoutedRunner(
        s, _loss, role_class={"a": 0, "b": 0, "neg": 0},
        role_dim={"a": VL // 2, "b": VL // 2, "neg": VL // 2},
        shard=w.shard, neg_role="neg", neg_shape=(8, 2), seed=3)


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
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
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
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    longest = {}
    for plane_ in ProfileData.from_file(path).planes:
        if plane_.name != "/host:CPU":
            continue
        for line in plane_.lines:
            for e in line.events:
                if e.name.startswith("adapm.") or e.name == "test.loop":
                    longest[e.name] = max(longest.get(e.name, 0.0),
                                          float(e.duration_ns))
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
                              idx, jnp.zeros((2, VL)), pooling="sum"),
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
