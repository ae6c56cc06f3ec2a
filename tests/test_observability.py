"""Observability tests.

Pre-existing surfaces (reference §5: PS_TRACE_KEYS trace events ->
traces.<rank>.tsv, PS_LOCALITY_STATS counters ->
locality_stats.rank.<r>.tsv, sync shutdown report) plus the unified
telemetry layer (ISSUE 2): metrics registry semantics, snapshot schema
stability, span traces, crash breadcrumbs, `--sys.metrics 0` inertness,
and TSV determinism."""
import json
import sys
import threading

import numpy as np
import pytest

import adapm_tpu
from adapm_tpu.base import CLOCK_MAX
from adapm_tpu.config import SystemOptions
from adapm_tpu.utils.stats import (LOCALITY_COLUMNS, TRACE_COLUMNS,
                                   parse_trace_spec)


def test_parse_trace_spec():
    assert len(parse_trace_spec("all", 10)) == 10
    ks = parse_trace_spec("3,7,7,1", 10)
    assert ks.tolist() == [1, 3, 7]
    r = parse_trace_spec("random-5-seed-3-range-0-100", 1000)
    assert len(r) <= 5 and r.max() < 100
    assert parse_trace_spec("", 10) is None


def test_trace_events_and_locality_files(tmp_path):
    opts = SystemOptions(trace_keys="all", locality_stats=True,
                         stats_out=str(tmp_path), sync_max_per_sec=0,
                         cache_slots_per_shard=16)
    srv = adapm_tpu.setup(32, 4, opts=opts)
    w0 = srv.make_worker(0)
    w1 = srv.make_worker(1)

    keys = np.arange(8, dtype=np.int64)
    w0.set(keys, np.ones((8, 4), np.float32))
    w0.pull_sync(keys)
    # both workers want key 5 -> replication; only w0 wants key 9 -> may
    # relocate
    w0.intent(np.array([5]), 0, CLOCK_MAX)
    w1.intent(np.array([5]), 0, CLOCK_MAX)
    w0.intent(np.array([9]), 0, CLOCK_MAX)
    srv.wait_sync()
    w0.pull_sync(np.array([5, 9]))
    files = srv.write_stats()
    srv.shutdown()

    paths = {p.split("/")[-1] for p in files}
    assert "traces.0.tsv" in paths
    assert "locality_stats.rank.0.tsv" in paths

    trace = (tmp_path / "traces.0.tsv").read_text().splitlines()
    events = {ln.split("\t")[2] for ln in trace[1:]}
    assert "ALLOC" in events and "INTENT_START" in events
    assert ("REPLICA_SETUP" in events) or ("RELOCATE" in events)

    loc = (tmp_path / "locality_stats.rank.0.tsv").read_text().splitlines()
    assert loc[0].startswith("key\taccesses")
    rows = {int(ln.split("\t")[0]): [int(x) for x in ln.split("\t")[1:]]
            for ln in loc[1:]}
    # every access count >= local count
    for k, (acc, local, _samp) in rows.items():
        assert acc >= local


@pytest.mark.parametrize("path", ["step", "staged", "scan"])
def test_per_key_locality_counts_device_routed_step(path):
    """--sys.stats.locality: a fused step records its host-known keys in
    the per-key counters (the hot loop is where the reference counts
    most accesses), once for each occurrence on every dispatch path
    (staging a batch ahead records nothing), and `local` by the mask of
    Server._route, here after a relocation. With the option off there
    are no per-key counters at all."""
    from adapm_tpu.ops import DeviceRoutedRunner

    off = adapm_tpu.setup(16, 8, opts=SystemOptions(sync_max_per_sec=0))
    assert off.locality is None
    off.shutdown()

    opts = SystemOptions(locality_stats=True, sync_max_per_sec=0)
    srv = adapm_tpu.setup(16, 8, opts=opts)
    w = srv.make_worker(0)
    w.set(np.arange(16), np.ones((16, 8), np.float32))
    moved = np.array([k for k in range(16)
                      if srv.ab.owner[k] != w.shard][:3], dtype=np.int64)
    w.intent(moved, 0, CLOCK_MAX)
    srv.wait_sync()
    assert (srv.ab.owner[moved] == w.shard).all()  # relocated here

    def loss_fn(embs, aux):
        return (embs["x"] ** 2).mean() + (embs["y"] ** 2).mean()

    runner = DeviceRoutedRunner(srv, loss_fn, role_class={"x": 0, "y": 0},
                                role_dim={"x": 4, "y": 4}, shard=w.shard)
    rng = np.random.default_rng(5)
    batches = [{"x": np.concatenate([moved, rng.integers(0, 16, 5)]),
                "y": rng.integers(0, 16, (4, 2)).astype(np.int64)}
               for _ in range(2 if path == "scan" else 1)]
    acc0, loc0 = srv.locality.accesses.copy(), srv.locality.local.copy()
    if path == "scan":
        runner.run_scan(batches, None, 0.1)
    else:
        stg = runner.prefetch_keys(batches[0]) if path == "staged" else None
        runner(batches[0], None, 0.1, staged=stg)

    flat = np.concatenate([k.ravel() for b in batches for k in b.values()])
    local = srv._route(flat, w.shard, record=False)[-1].astype(bool)
    assert local[np.isin(flat, moved)].all() and not local.all()
    assert np.array_equal(srv.locality.accesses - acc0,
                          np.bincount(flat, minlength=16))
    assert np.array_equal(srv.locality.local - loc0,
                          np.bincount(flat[local], minlength=16))
    # the same accesses, counted in the program, feed the summary
    counts = runner.locality_counts()
    assert counts["params"] == len(flat)
    assert counts["params_local"] == int(local.sum())
    srv.locality_summary()
    srv.shutdown()


def test_sync_report_string():
    opts = SystemOptions(sync_max_per_sec=0)
    srv = adapm_tpu.setup(8, 2, opts=opts)
    w = srv.make_worker(0)
    w.intent(np.arange(4), 0, 10)
    srv.wait_sync()
    rep = srv.sync.report()
    assert "rounds=" in rep and "intents=" in rep
    srv.shutdown()


# ---------------------------------------------------------------------------
# unified telemetry (ISSUE 2): registry semantics
# ---------------------------------------------------------------------------


def test_counter_sharded_across_threads():
    from adapm_tpu.obs.metrics import Counter
    c = Counter("t.c")
    threads = [threading.Thread(
        target=lambda: [c.inc() for _ in range(1000)])
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 4000


def test_histogram_bucket_counts():
    from adapm_tpu.obs.metrics import Histogram
    h = Histogram("t.h", bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 0.1, 1.0, 5.0, 50.0, 500.0):
        h.observe(v)
    s = h.snap()
    # bisect_left: v <= bound lands in that bound's bucket, the last
    # bucket is the +inf overflow
    assert s["buckets"] == [3, 1, 1, 1]
    assert s["count"] == 6 and sum(s["buckets"]) == s["count"]
    assert s["max"] == 500.0
    assert abs(s["sum"] - 556.6) < 1e-9
    assert s["bounds"] == [1.0, 10.0, 100.0]


def test_duplicate_metric_name_check():
    from adapm_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("a.b")
    # two subsystems cannot silently split one counter...
    with pytest.raises(ValueError):
        reg.counter("a.b")
    # ...nor register different kinds under one name, even shared
    with pytest.raises(ValueError):
        reg.histogram("a.b", shared=True)
    # declared-shared metrics are the get-or-create exception
    c1 = reg.counter("a.c", shared=True)
    c2 = reg.counter("a.c", shared=True)
    assert c1 is c2


def test_registry_snapshot_sections_and_gauges():
    from adapm_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("kv.ops").inc(3)
    reg.gauge("staging.occ", fn=lambda: 7)
    reg.histogram("sync.lat_s").observe(0.01)
    s = reg.snapshot()
    assert s["kv"]["ops"] == 3
    assert s["staging"]["occ"] == 7
    assert s["sync"]["lat_s"]["count"] == 1


def test_counter_group_legacy_dict_api():
    from adapm_tpu.obs.metrics import CounterGroup, MetricsRegistry
    reg = MetricsRegistry()
    g = CounterGroup(reg, "prefetch", ("hits", "staged"))
    g.inc("hits")
    g["staged"] += 2          # legacy += path applies the delta
    assert g["hits"] == 1 and g["staged"] == 2
    assert dict(g.items()) == {"hits": 1, "staged": 2}
    assert reg.snapshot()["prefetch"] == {"hits": 1, "staged": 2}


# ---------------------------------------------------------------------------
# unified telemetry: Server.metrics_snapshot end to end
# ---------------------------------------------------------------------------


def _run_instrumented(opts, n_keys=32, vlen=4):
    srv = adapm_tpu.setup(n_keys, vlen, opts=opts, num_workers=2)
    w = srv.make_worker(0)
    keys = np.arange(8, dtype=np.int64)
    w.set(keys, np.ones((8, vlen), np.float32))
    w.pull_sync(keys)
    w.intent(keys, 0, 100)
    if srv.prefetch is not None:
        srv.prefetch.flush()
    w.pull_sync(keys)
    w.push(keys, np.ones((8, vlen), np.float32))
    srv.wait_sync()
    return srv, w


def test_metrics_snapshot_schema_stable():
    srv, w = _run_instrumented(SystemOptions(sync_max_per_sec=0,
                                             prefetch_pull="always"))
    snap = srv.metrics_snapshot()
    # the documented schema contract (docs/OBSERVABILITY.md); v3 = the
    # PR 4 serve section (the online serving plane's metrics +
    # readiness; {} until a ServePlane is attached); v4 = the PR 5 tier
    # section (tiered-storage hot-hit/promotion metrics; {} while
    # --sys.tier is off); v6 = the PR 7 flight/slo sections
    # (request-flight tracing + the SLO autopilot; flight carries only
    # the crash-ride flight-recorder summary until --sys.trace.flight,
    # slo is {} until --sys.serve.slo_ms)
    assert snap["schema_version"] == 17 and snap["metrics_enabled"]
    assert snap["serve"] == {}  # no ServePlane on this server
    assert snap["tier"] == {}   # --sys.tier off on this server
    assert snap["slo"] == {}    # no --sys.serve.slo_ms target set
    # flight tracing is off, but the executor flight-recorder rides
    # --sys.crash_dumps (default on): the section carries its summary
    assert set(snap["flight"]) == {"recorder"}
    assert snap["flight"]["recorder"]["programs_recorded"] >= 0
    for sec in srv._SNAPSHOT_SECTIONS:
        assert isinstance(snap[sec], dict), sec
    # v2 sync surface: shipped vs considered + table-occupancy gauges
    assert snap["sync"]["keys_shipped"] == snap["sync"]["keys_synced"]
    assert snap["sync"]["keys_considered"] >= snap["sync"]["keys_synced"]
    assert snap["sync"]["replicas_live"] >= 0
    assert 0.0 <= snap["sync"]["dirty_fraction"] <= 1.0
    assert "replicas_live.c0" in snap["sync"]
    # kv: latency histograms + op counters + the ts=-1 rate
    assert snap["kv"]["pull_s"]["count"] >= 2
    assert snap["kv"]["push_s"]["count"] >= 1
    assert snap["kv"]["pull_ops"] >= 2
    assert 0.0 <= snap["kv"]["local_answer_frac"] <= 1.0
    # prefetch / plan-cache / staging / sync coverage
    assert snap["prefetch"]["staged"] >= 1 and snap["prefetch"]["hits"] >= 1
    assert snap["plan_cache"]["hits"] + snap["plan_cache"]["misses"] >= 1
    assert snap["staging"]["rows_hwm"] >= 1
    assert snap["sync"]["rounds"] >= 1
    assert snap["sync"]["round_s"]["count"] >= 1
    # JSON-serializable as-is (bench embeds it in the artifact)
    json.dumps(snap)
    # schema stability: a second snapshot has the same key structure
    snap2 = srv.metrics_snapshot()
    assert set(snap2) == set(snap)
    for sec in srv._SNAPSHOT_SECTIONS:
        assert set(snap2[sec]) == set(snap[sec]), sec
    srv.shutdown()


def test_snapshot_is_single_source_for_legacy_views():
    """The pre-existing ad-hoc surfaces are views over the registry:
    the numbers agree by construction."""
    srv, w = _run_instrumented(SystemOptions(sync_max_per_sec=0,
                                             prefetch_pull="always"))
    snap = srv.metrics_snapshot()
    for k, v in srv.prefetch.stats.items():
        assert snap["prefetch"][k] == v
    pc = srv._plan_cache.stats()
    for k in ("hits", "misses", "stale"):
        assert snap["plan_cache"][k] == pc[k]
    srv.shutdown()


def test_metrics_off_empty_registry_and_no_reporter_import():
    """--sys.metrics 0: null registry (empty snapshot, no metric names,
    no latency bracketing) and ZERO imports of the reporter module."""
    sys.modules.pop("adapm_tpu.obs.reporter", None)
    srv, w = _run_instrumented(SystemOptions(sync_max_per_sec=0,
                                             metrics=False))
    assert not srv.obs.enabled
    assert srv.obs.names() == []
    snap = srv.metrics_snapshot()
    assert snap["metrics_enabled"] is False
    for sec in srv._SNAPSHOT_SECTIONS:
        assert snap[sec] == {}, sec
    assert w._h_pull is None  # hot path skips even the perf_counter
    # prefetch's own accounting survives metrics-off (standalone view)
    assert srv.prefetch.stats["hits"] >= 1
    assert "adapm_tpu.obs.reporter" not in sys.modules
    srv.shutdown()


def test_metrics_reporter_runs_and_stops():
    srv, w = _run_instrumented(SystemOptions(sync_max_per_sec=0,
                                             metrics_report_s=0.05))
    assert srv._reporter is not None
    from adapm_tpu.obs.reporter import _fmt
    line = _fmt(srv.obs.snapshot())
    assert "pull=" in line  # the one-line summary carries kv latency
    srv.shutdown()
    assert srv._reporter is None


# ---------------------------------------------------------------------------
# unified telemetry: span traces + crash breadcrumbs
# ---------------------------------------------------------------------------


def test_span_trace_chrome_json(tmp_path):
    opts = SystemOptions(sync_max_per_sec=0, trace_spans=True,
                         stats_out=str(tmp_path), prefetch_pull="always")
    srv, w = _run_instrumented(opts)
    path = srv.write_trace()
    srv.shutdown()
    doc = json.load(open(path))
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert evs, "no complete events recorded"
    names = {e["name"] for e in evs}
    # the instrumented phases of this scenario all appear
    for must in ("kv.pull", "kv.push", "kv.set", "kv.plan_pull",
                 "sync.round", "sync.drain_intents", "prefetch.stage",
                 "prefetch.take"):
        assert must in names, must
    for e in evs:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == 0
    # thread metadata present (Perfetto track naming)
    assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
               for e in doc["traceEvents"])


def test_crash_dump_and_breadcrumb(tmp_path):
    import faulthandler
    opts = SystemOptions(sync_max_per_sec=0, trace_spans=True,
                         stats_out=str(tmp_path))
    srv, w = _run_instrumented(opts)
    assert faulthandler.is_enabled()
    import os
    assert os.path.exists(srv.crash_dump_path)
    bc = sorted(tmp_path.glob("adapm_breadcrumb.*.txt"))
    assert bc, "breadcrumb file missing"
    # the last-open-span breadcrumb names an instrumented phase
    content = bc[-1].read_text().split()[0]
    assert content.split(".")[0] in ("kv", "sync", "prefetch",
                                     "collective")
    srv.shutdown()


# ---------------------------------------------------------------------------
# TSV determinism + event ordering (satellites)
# ---------------------------------------------------------------------------


def test_trace_event_ordering_and_column_schema(tmp_path):
    opts = SystemOptions(trace_keys="all", locality_stats=True,
                         stats_out=str(tmp_path), sync_max_per_sec=0,
                         cache_slots_per_shard=16, metrics=False)
    srv = adapm_tpu.setup(32, 4, opts=opts)
    w0 = srv.make_worker(0)
    w1 = srv.make_worker(1)
    keys = np.arange(8, dtype=np.int64)
    w0.set(keys, np.ones((8, 4), np.float32))
    # shared interest with a FINITE window -> replica now, drop later
    w0.intent(np.array([5]), 0, 1)
    w1.intent(np.array([5]), 0, 1)
    srv.wait_sync()
    w0.pull_sync(np.array([5]))
    for _ in range(4):  # advance past the intent window
        w0.advance_clock()
        w1.advance_clock()
    srv.wait_sync()  # expiry: INTENT_STOP + REPLICA_DROP
    files = srv.write_stats()
    srv.shutdown()

    trace = (tmp_path / "traces.0.tsv").read_text().splitlines()
    assert trace[0] == "\t".join(TRACE_COLUMNS)
    rows = [ln.split("\t") for ln in trace[1:]]
    # deterministic order: rows sorted by (time, key, event, shard)
    keyed = [(float(t), int(k), e, int(s)) for t, k, e, s in rows]
    assert keyed == sorted(keyed)
    by_key = {}
    for t, k, e, s in keyed:
        by_key.setdefault(k, []).append((t, e))
    # ALLOC precedes REPLICA_SETUP for every replicated key
    for k, evs in by_key.items():
        times = {e: t for t, e in reversed(evs)}  # first occurrence
        if "REPLICA_SETUP" in times:
            assert "ALLOC" in times
            assert times["ALLOC"] <= times["REPLICA_SETUP"], k
        # INTENT_START/STOP pairing: stops never exceed starts, and the
        # first start precedes the first stop
        starts = [t for t, e in evs if e == "INTENT_START"]
        stops = [t for t, e in evs if e == "INTENT_STOP"]
        assert len(stops) <= len(starts)
        if stops:
            assert min(starts) <= min(stops)
    # the finite-window scenario actually produced a paired stop
    assert any(e == "INTENT_STOP" for _, k, e, _ in keyed)

    loc = (tmp_path / "locality_stats.rank.0.tsv").read_text().splitlines()
    assert loc[0] == "\t".join(LOCALITY_COLUMNS)
    ks = [int(ln.split("\t")[0]) for ln in loc[1:]]
    assert ks == sorted(ks)


def test_stopwatch_concurrent_readers():
    from adapm_tpu.utils import Stopwatch
    sw = Stopwatch()
    stop = threading.Event()
    errs = []

    def hammer():
        try:
            while not stop.is_set():
                sw.start()
                sw.stop()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    def read():
        try:
            last = -1.0
            while not stop.is_set():
                v = sw.elapsed_s
                assert v >= 0.0
                # cumulative elapsed never regresses while stopped jobs
                # only add time
                assert v >= last - 1e-3
                last = v
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=hammer),
               threading.Thread(target=hammer),
               threading.Thread(target=read)]
    for t in threads:
        t.start()
    import time
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert not errs, errs
    assert sw.elapsed_s >= 0.0
