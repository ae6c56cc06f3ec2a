"""Config knobs that tune the data plane: --sys.sync.threshold,
--sampling.batch_size, remote_bucket_min (reference sync_manager.h:805-814,
sampling.h:394-405)."""
import numpy as np

import adapm_tpu
from adapm_tpu.base import CLOCK_MAX, MgmtTechniques
from adapm_tpu.config import SystemOptions


def _replicated_key(srv, w0):
    """Force a replica of a non-local key onto w0's shard."""
    key = next(k for k in range(srv.num_keys)
               if srv.ab.owner[k] != w0.shard)
    w0.intent(np.array([key]), 0, CLOCK_MAX)
    srv.wait_sync()
    assert srv.ab.cache_slot[w0.shard, key] >= 0, "replica not created"
    return key


def test_sync_threshold_holds_back_small_deltas():
    opts = SystemOptions(techniques=MgmtTechniques.REPLICATION_ONLY,
                         sync_threshold=1e-3, sync_max_per_sec=0,
                         cache_slots_per_shard=8)
    srv = adapm_tpu.setup(16, 4, opts=opts)
    w0 = srv.make_worker(0)
    w0.set(np.arange(16), np.ones((16, 4), np.float32))
    key = _replicated_key(srv, w0)

    # tiny delta: below threshold, stays pending through a sync round
    w0.push(np.array([key]), np.full((1, 4), 1e-5, np.float32))
    srv.wait_sync()
    assert np.allclose(srv.read_main(np.array([key])), 1.0)
    # read-your-writes on the replica still holds
    assert np.allclose(w0.pull_sync(np.array([key])), 1.0 + 1e-5)

    # once the delta grows past the threshold it ships
    w0.push(np.array([key]), np.ones((1, 4), np.float32))
    srv.wait_sync()
    assert np.allclose(srv.read_main(np.array([key])), 2.0 + 1e-5)

    # quiesce flushes unconditionally — no delta is ever lost
    w0.push(np.array([key]), np.full((1, 4), 1e-5, np.float32))
    srv.quiesce()
    assert np.allclose(srv.read_main(np.array([key])), 2.0 + 2e-5)
    srv.shutdown()


def test_sync_threshold_drop_flushes_pending_delta():
    """Replica drop (intent expiry) must flush even sub-threshold deltas."""
    opts = SystemOptions(techniques=MgmtTechniques.REPLICATION_ONLY,
                         sync_threshold=1e-3, sync_max_per_sec=0,
                         cache_slots_per_shard=8)
    srv = adapm_tpu.setup(16, 4, opts=opts)
    w0 = srv.make_worker(0)
    w0.set(np.arange(16), np.ones((16, 4), np.float32))
    key = next(k for k in range(srv.num_keys)
               if srv.ab.owner[k] != w0.shard)
    w0.intent(np.array([key]), 0, 2)  # expires at clock 3
    srv.wait_sync()
    assert srv.ab.cache_slot[w0.shard, key] >= 0
    w0.push(np.array([key]), np.full((1, 4), 1e-5, np.float32))
    for _ in range(4):
        w0.advance_clock()
    srv.wait_sync()  # intent expired -> replica dropped, delta flushed
    assert srv.ab.cache_slot[w0.shard, key] < 0, "replica should be dropped"
    assert np.allclose(srv.read_main(np.array([key])), 1.0 + 1e-5)
    srv.shutdown()


def test_sampling_batch_size_buffers_rng_draws():
    calls = []

    def sample_fn(n, rng):
        calls.append(n)
        return rng.integers(0, 32, n)

    opts = SystemOptions(sampling_scheme="naive", sampling_batch_size=64,
                         sync_max_per_sec=0)
    srv = adapm_tpu.setup(32, 4, opts=opts)
    w = srv.make_worker(0)
    w.set(np.arange(32), np.ones((32, 4), np.float32))
    srv.enable_sampling_support(sample_fn)
    for _ in range(8):
        h = w.prepare_sample(5)
        keys, vals = w.pull_sample(h)
        assert len(keys) == 5 and vals.shape == (5, 4)
        w.finish_sample(h)
    # 8 * 5 = 40 draws served by a single 64-key buffered call
    assert calls == [64], calls
    # large draws bypass the buffer
    h = w.prepare_sample(200)
    keys, _ = w.pull_sample(h)
    assert len(keys) == 200
    assert calls == [64, 200], calls
    srv.shutdown()


def test_remote_bucket_min_sets_padding_floor():
    opts = SystemOptions(remote_bucket_min=32, sync_max_per_sec=0)
    srv = adapm_tpu.setup(64, 4, opts=opts)
    assert all(s.bucket_min == 32 for s in srv.stores)
    w = srv.make_worker(0)
    w.set(np.arange(64), np.ones((64, 4), np.float32))
    # tiny op still correct under the larger padding floor
    w.push(np.array([3]), np.full((1, 4), 2.0, np.float32))
    srv.block()
    assert np.allclose(srv.read_main(np.array([3])), 3.0)
    srv.shutdown()


def test_dcn_threads_sizes_pm_executors():
    """--sys.dcn_threads (reference --sys.zmq_threads analog) sizes the
    GlobalPM's executors; single-process has no PM, so check the parse
    path and the multi-process consumption site directly."""
    import argparse

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    opts = SystemOptions.from_args(p.parse_args(["--sys.dcn_threads", "3"]))
    assert opts.dcn_threads == 3
    # behavior of the consumption site: GlobalPM sizes its executors via
    # executor_widths (end-to-end coverage lives in the mp suite)
    from adapm_tpu.parallel.pm import executor_widths
    assert executor_widths(opts) == (3, 2)
    wide = SystemOptions.from_args(p.parse_args(["--sys.dcn_threads", "8"]))
    assert executor_widths(wide) == (8, 4)


def test_serve_knobs_round_trip():
    """--sys.serve.* parse into the options ServePlane consumes
    (ISSUE 4 satellite)."""
    import argparse

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    assert (dflt.serve_max_batch, dflt.serve_max_wait_us,
            dflt.serve_queue, dflt.serve_deadline_ms) == (64, 200,
                                                          1024, 0.0)
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.serve.max_batch", "16", "--sys.serve.max_wait_us", "500",
         "--sys.serve.queue", "256", "--sys.serve.deadline_ms", "50"]))
    assert on.serve_max_batch == 16 and on.serve_max_wait_us == 500
    assert on.serve_queue == 256 and on.serve_deadline_ms == 50.0


def test_serve_knobs_rejected_at_parse_time():
    """Out-of-range / inconsistent --sys.serve.* combinations fail
    loudly at parse time, not when the first lookup misbehaves."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    bad = (["--sys.serve.max_batch", "0"],
           ["--sys.serve.max_wait_us", "-1"],
           ["--sys.serve.queue", "0"],
           ["--sys.serve.deadline_ms", "-5"],
           # inconsistent: queue bound below max_batch makes the
           # configured batch size unreachable
           ["--sys.serve.queue", "8", "--sys.serve.max_batch", "16"])
    for argv in bad:
        with pytest.raises(ValueError):
            SystemOptions.from_args(p.parse_args(argv))
    # hand-built options are validated again at ServePlane construction
    with pytest.raises(ValueError):
        SystemOptions(serve_max_batch=-3).validate_serve()


def test_flight_and_slo_knobs_round_trip_and_rejection():
    """--sys.trace.flight / --sys.serve.slo_ms parse into the options
    the flight tracer and SLO controller consume, and invalid
    combinations fail loudly at parse time (ISSUE 7)."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    # both DEFAULT OFF: no tracer, no controller, static knob path
    assert dflt.trace_flight is False and dflt.trace_flight_out is None
    assert dflt.serve_slo_ms == 0.0
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.trace.flight", "1",
         "--sys.trace.flight_out", "/tmp/f.json",
         "--sys.serve.slo_ms", "12.5"]))
    assert on.trace_flight is True
    assert on.trace_flight_out == "/tmp/f.json"
    assert on.serve_slo_ms == 12.5
    # negative target / controller without its histogram: rejected
    with pytest.raises(ValueError):
        SystemOptions.from_args(p.parse_args(
            ["--sys.serve.slo_ms", "-1"]))
    with pytest.raises(ValueError):
        SystemOptions.from_args(p.parse_args(
            ["--sys.serve.slo_ms", "10", "--sys.metrics", "0"]))


def test_tier_knobs_round_trip_and_rejection():
    """--sys.tier.* parse into the options the TierManager consumes,
    and bad ranges fail loudly at parse time (ISSUE 5)."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    assert (dflt.tier, dflt.tier_hot_rows, dflt.tier_pin_intent,
            dflt.tier_demote_batch) == (False, 65536, True, 1024)
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.tier", "1", "--sys.tier.hot_rows", "4096",
         "--sys.tier.pin_intent", "0", "--sys.tier.demote_batch",
         "128"]))
    assert on.tier and on.tier_hot_rows == 4096
    assert not on.tier_pin_intent and on.tier_demote_batch == 128
    for argv in (["--sys.tier", "1", "--sys.tier.hot_rows", "4"],
                 ["--sys.tier", "1", "--sys.tier.demote_batch", "0"]):
        with pytest.raises(ValueError):
            SystemOptions.from_args(p.parse_args(argv))
    # tier off: hot_rows range is irrelevant and must not reject
    SystemOptions.from_args(p.parse_args(["--sys.tier.hot_rows", "4"]))


def test_cache_slots_flag_round_trip_and_rejection():
    """--sys.cache_slots_per_shard reaches the option the stores size
    their replica pools by, the app's make_server passes it on, and a
    negative count fails at parse time."""
    import argparse

    import pytest

    from adapm_tpu.apps import knowledge_graph_embeddings as kge
    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    assert SystemOptions.from_args(
        p.parse_args([])).cache_slots_per_shard == 0
    on = SystemOptions.from_args(
        p.parse_args(["--sys.cache_slots_per_shard", "24"]))
    assert on.cache_slots_per_shard == 24
    with pytest.raises(ValueError, match="cache_slots_per_shard"):
        SystemOptions.from_args(
            p.parse_args(["--sys.cache_slots_per_shard", "-1"]))
    args = kge.build_parser().parse_args(
        ["--num_shards", "2", "--sys.cache_slots_per_shard", "24"])
    srv = kge.make_server(args, 64, 4, num_workers=2)
    try:
        assert [st.cache_slots for st in srv.stores] == [24]
    finally:
        srv.shutdown()


def test_compression_knobs_round_trip_and_rejection():
    """--sys.tier.cold_dtype / --sys.sync.compress parse into the
    options the compression plane consumes, and invalid names or
    inconsistent combinations fail loudly at parse time (ISSUE 8)."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    # both DEFAULT to the pre-PR exact wire: fp32 at rest, no sync
    # compression (the bit-identity pin run_tests.sh guards)
    assert dflt.tier_cold_dtype == "fp32"
    assert dflt.sync_compress == "off"
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.tier", "1", "--sys.tier.cold_dtype", "fp16",
         "--sys.sync.compress", "fp16"]))
    assert on.tier_cold_dtype == "fp16" and on.sync_compress == "fp16"
    i8 = SystemOptions.from_args(p.parse_args(
        ["--sys.tier", "1", "--sys.tier.cold_dtype", "int8",
         "--sys.sync.compress", "int8"]))
    assert i8.tier_cold_dtype == "int8" and i8.sync_compress == "int8"
    # invalid dtype names: argparse choices reject unknown wire formats
    # before the options object even exists
    with pytest.raises(SystemExit):
        p.parse_args(["--sys.tier.cold_dtype", "fp8"])
    with pytest.raises(SystemExit):
        p.parse_args(["--sys.sync.compress", "bf16"])
    # hand-built options (no argparse choices) reject through validate
    with pytest.raises(ValueError):
        SystemOptions(tier_cold_dtype="fp8").validate_serve()
    with pytest.raises(ValueError):
        SystemOptions(sync_compress="bf16").validate_serve()
    # int8 sync without metrics: the EF residual loop would be invisible
    # (no sync.ef_residual_norm gauge) — a silent-quality-loss trap
    with pytest.raises(ValueError):
        SystemOptions.from_args(p.parse_args(
            ["--sys.sync.compress", "int8", "--sys.metrics", "0"]))
    # fp16 sync is allowed without metrics (residual bounded by the
    # representation, not the feedback loop alone)
    SystemOptions.from_args(p.parse_args(
        ["--sys.sync.compress", "fp16", "--sys.metrics", "0"]))
    # compression requires the dirty filter: the full-resync path has
    # no epoch state for residual-parked-but-clean replicas
    with pytest.raises(ValueError):
        SystemOptions.from_args(p.parse_args(
            ["--sys.sync.compress", "fp16", "--sys.sync.dirty_only", "0"]))


def test_collective_sync_knobs():
    """--sys.collective_sync / --sys.collective_bucket parse into the
    options GlobalPM consults when choosing the sync data plane."""
    import argparse

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    off = SystemOptions.from_args(p.parse_args([]))
    assert off.collective_sync is False and off.collective_bucket == 1024
    assert off.collective_cadence == 0
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.collective_sync", "1", "--sys.collective_bucket", "256",
         "--sys.collective_cadence", "8"]))
    assert on.collective_sync is True and on.collective_bucket == 256
    assert on.collective_cadence == 8


def test_fault_and_ckpt_knobs_round_trip_and_rejection():
    """--sys.fault.* / --sys.checkpoint.* parse into the options the
    fault plane, executor policy, and periodic checkpointer consume
    (ISSUE 10); bad combinations fail loudly at parse time."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    # defaults: NO injection plane, inert retry policy, no periodic ckpt
    assert dflt.fault_spec == "" and dflt.fault_seed == 0
    assert (dflt.fault_retries, dflt.fault_watchdog_s) == (3, 30.0)
    assert dflt.ckpt_every_s == 0.0 and dflt.ckpt_path is None
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.fault.spec", "sync.round=0.2,serve.drain=0.1",
         "--sys.fault.seed", "7", "--sys.fault.retries", "5",
         "--sys.fault.backoff_ms", "2", "--sys.fault.watchdog_s", "9",
         "--sys.checkpoint.every", "30",
         "--sys.checkpoint.path", "/tmp/chain"]))
    assert on.fault_spec == "sync.round=0.2,serve.drain=0.1"
    assert on.fault_seed == 7 and on.fault_retries == 5
    assert on.fault_backoff_ms == 2.0 and on.fault_watchdog_s == 9.0
    assert on.ckpt_every_s == 30.0 and on.ckpt_path == "/tmp/chain"
    bad = (["--sys.fault.spec", "oops"],           # not point=prob
           ["--sys.fault.spec", "x=1.5"],          # prob out of range
           ["--sys.fault.retries", "-1"],
           ["--sys.fault.watchdog_s", "0"],
           ["--sys.checkpoint.every", "-2"],
           # periodic checkpoints without a chain directory
           ["--sys.checkpoint.every", "30"])
    for argv in bad:
        with pytest.raises(ValueError):
            SystemOptions.from_args(p.parse_args(argv))
    # hand-built options are validated the same way
    with pytest.raises(ValueError):
        SystemOptions(fault_spec="x=nan").validate_serve()


def test_lint_lockorder_knob_round_trip_and_rejection():
    """--sys.lint.lockorder (ISSUE 11): parses into the option the
    Server's lock wiring consumes, defaults OFF (the skip-wrapper
    shape — plain RLocks, no sentinel — is pinned by
    tests/test_lint.py::test_lockorder_skip_wrapper_shape), and a
    non-integer value is rejected at the parser."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    assert dflt.lint_lockorder is False
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.lint.lockorder", "1"]))
    assert on.lint_lockorder is True
    off = SystemOptions.from_args(p.parse_args(
        ["--sys.lint.lockorder", "0"]))
    assert off.lint_lockorder is False
    with pytest.raises(SystemExit):  # argparse type=int rejection
        p.parse_args(["--sys.lint.lockorder", "maybe"])


def test_episode_batches_knob_round_trip_and_rejection():
    """--sys.episode.batches (ISSUE 14): parses into the option
    EpisodicRunner defaults from, defaults to 8, and zero is rejected
    by validate_serve at parse time (an episode must hold a batch)."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    assert dflt.episode_batches == 8
    got = SystemOptions.from_args(p.parse_args(
        ["--sys.episode.batches", "3"]))
    assert got.episode_batches == 3
    with pytest.raises(ValueError, match="episode.batches"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.episode.batches", "0"]))


def test_workload_trace_knobs_round_trip_and_rejection():
    """--sys.trace.workload / --sys.trace.workload_keys (ISSUE 15):
    parse into the options the WorkloadTraceRecorder consumes, default
    OFF (no recorder, zero wtrace.* names — pinned by
    tests/test_wtrace.py and scripts/metrics_overhead_check.py), and a
    zero key budget is rejected at parse time AND on hand-built
    options."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    assert dflt.trace_workload is None
    assert dflt.trace_workload_keys == 4096
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.trace.workload", "/tmp/run.wtrace",
         "--sys.trace.workload_keys", "256"]))
    assert on.trace_workload == "/tmp/run.wtrace"
    assert on.trace_workload_keys == 256
    # zero/negative key budget: an unreplayable trace, rejected loudly
    with pytest.raises(ValueError, match="workload_keys"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.trace.workload", "/tmp/run.wtrace",
             "--sys.trace.workload_keys", "0"]))
    with pytest.raises(ValueError, match="workload_keys"):
        SystemOptions(trace_workload_keys=-1).validate_serve()
    # non-integer budget rejected by argparse itself
    with pytest.raises(SystemExit):
        p.parse_args(["--sys.trace.workload_keys", "lots"])


def test_bag_and_costs_knobs_round_trip_and_rejection():
    """--sys.serve.bags / --sys.costs.table / --sys.costs.calibrate
    (ISSUE 16): parse into the options the serve batcher's bag
    dispatch and the kernel cost table consume; bags default ON (the
    fused path), the cost table defaults absent; an empty table path
    and a calibrate without a table are rejected at parse time AND on
    hand-built options."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    assert dflt.serve_bags is True
    assert dflt.costs_table is None
    assert dflt.costs_calibrate is False
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.serve.bags", "0",
         "--sys.costs.table", "/tmp/costs.json",
         "--sys.costs.calibrate", "1"]))
    assert on.serve_bags is False
    assert on.costs_table == "/tmp/costs.json"
    assert on.costs_calibrate is True
    # an empty table path can persist nothing — rejected loudly
    with pytest.raises(ValueError, match="costs.table"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.costs.table", ""]))
    with pytest.raises(ValueError, match="costs.table"):
        SystemOptions(costs_table="").validate_serve()
    # a calibration pass with nowhere to persist is a no-op trap
    with pytest.raises(ValueError, match="costs.calibrate"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.costs.calibrate", "1"]))
    with pytest.raises(ValueError, match="costs.calibrate"):
        SystemOptions(costs_calibrate=True).validate_serve()
    # non-integer bag flag rejected by argparse itself
    with pytest.raises(SystemExit):
        p.parse_args(["--sys.serve.bags", "maybe"])


def test_decision_trace_knobs_round_trip_and_rejection():
    """--sys.trace.decisions / --sys.trace.decisions_window /
    --sys.trace.spans.max_events (ISSUE 17): parse into the options
    the DecisionRecorder and SpanTracer consume, decisions default OFF
    (no recorder, zero decision.* names — pinned by
    tests/test_decisions.py and scripts/metrics_overhead_check.py);
    an empty .dtrace path, a zero follow window, and a sub-1000 span
    bound are each rejected at parse time AND on hand-built options."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    assert dflt.trace_decisions is None
    assert dflt.trace_decisions_window == 8
    assert dflt.trace_spans_max_events == 1_000_000
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.trace.decisions", "/tmp/run.dtrace",
         "--sys.trace.decisions_window", "16",
         "--sys.trace.spans.max_events", "5000"]))
    assert on.trace_decisions == "/tmp/run.dtrace"
    assert on.trace_decisions_window == 16
    assert on.trace_spans_max_events == 5000
    # an empty path can flush nothing — rejected loudly
    with pytest.raises(ValueError, match="trace.decisions"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.trace.decisions", ""]))
    with pytest.raises(ValueError, match="trace.decisions"):
        SystemOptions(trace_decisions="").validate_serve()
    # a zero-event follow window can never resolve an outcome
    with pytest.raises(ValueError, match="decisions_window"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.trace.decisions", "/tmp/run.dtrace",
             "--sys.trace.decisions_window", "0"]))
    with pytest.raises(ValueError, match="decisions_window"):
        SystemOptions(trace_decisions_window=0).validate_serve()
    # a tiny span buffer silently truncates every trace — floor 1000
    with pytest.raises(ValueError, match="spans.max_events"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.trace.spans.max_events", "100"]))
    with pytest.raises(ValueError, match="spans.max_events"):
        SystemOptions(trace_spans_max_events=999).validate_serve()
    # non-integer values rejected by argparse itself
    with pytest.raises(SystemExit):
        p.parse_args(["--sys.trace.decisions_window", "soon"])


def test_policy_knobs_round_trip_and_rejection():
    """--sys.policy.{reloc,tier,sync,serve}/file/shadow (ISSUE 18):
    parse into the options PolicyPlane consumes, everything defaults
    OFF (no plane, zero policy.* names — pinned by tests/test_policy.py
    and scripts/metrics_overhead_check.py); an unknown mode, an empty
    artifact path, and learned/shadow without a file are each rejected
    at parse time AND on hand-built options."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    assert dflt.policy_reloc == "heuristic"
    assert dflt.policy_tier == "heuristic"
    assert dflt.policy_sync == "heuristic"
    assert dflt.policy_serve == "heuristic"
    assert dflt.policy_file is None
    assert dflt.policy_shadow is False
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.policy.file", "/tmp/policy.json",
         "--sys.policy.tier", "learned",
         "--sys.policy.serve", "learned",
         "--sys.policy.shadow", "1"]))
    assert on.policy_file == "/tmp/policy.json"
    assert on.policy_tier == "learned"
    assert on.policy_serve == "learned"
    assert on.policy_reloc == "heuristic"  # untouched planes stay off
    assert on.policy_sync == "heuristic"
    assert on.policy_shadow is True
    # unknown mode rejected by argparse choices AND hand-built options
    with pytest.raises(SystemExit):
        p.parse_args(["--sys.policy.tier", "oracle"])
    with pytest.raises(ValueError, match="policy.tier"):
        SystemOptions(policy_tier="oracle",
                      policy_file="/tmp/p.json").validate_serve()
    # an empty artifact path can load nothing — rejected loudly
    with pytest.raises(ValueError, match="policy.file"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.policy.file", ""]))
    with pytest.raises(ValueError, match="policy.file"):
        SystemOptions(policy_file="").validate_serve()
    # learned mode without an artifact has nothing to consult
    with pytest.raises(ValueError, match="policy.file"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.policy.sync", "learned"]))
    with pytest.raises(ValueError, match="policy.file"):
        SystemOptions(policy_sync="learned").validate_serve()
    # shadow mode scores the trained policy — meaningless without one
    with pytest.raises(ValueError, match="policy.shadow"):
        SystemOptions.from_args(p.parse_args(
            ["--sys.policy.shadow", "1"]))
    with pytest.raises(ValueError, match="policy.shadow"):
        SystemOptions(policy_shadow=True).validate_serve()
    # non-integer shadow flag rejected by argparse itself
    with pytest.raises(SystemExit):
        p.parse_args(["--sys.policy.shadow", "maybe"])


def test_net_knobs_round_trip_and_rejection():
    """--sys.net.{backend,queue,timeout_ms,heartbeat_ms} parse into
    the options the NetPort backends consume, with bad values failing
    loudly at parse time (ISSUE 19 satellite)."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    assert (dflt.net_backend, dflt.net_queue, dflt.net_timeout_ms,
            dflt.net_heartbeat_ms) == ("auto", 64, 5000.0, 100.0)
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.net.backend", "tcp", "--sys.net.queue", "128",
         "--sys.net.timeout_ms", "750", "--sys.net.heartbeat_ms",
         "40"]))
    assert on.net_backend == "tcp" and on.net_queue == 128
    assert on.net_timeout_ms == 750.0 and on.net_heartbeat_ms == 40.0
    bad = (["--sys.net.backend", "carrier-pigeon"],
           ["--sys.net.queue", "0"],
           ["--sys.net.timeout_ms", "0"],
           ["--sys.net.heartbeat_ms", "-5"])
    for argv in bad:
        with pytest.raises(ValueError):
            SystemOptions.from_args(p.parse_args(argv))
    # hand-built options are validated again at server construction
    with pytest.raises(ValueError, match="net.backend"):
        SystemOptions(net_backend="ipx").validate_serve()
    with pytest.raises(ValueError, match="net.queue"):
        SystemOptions(net_queue=-1).validate_serve()


def test_stream_knobs_round_trip_and_rejection():
    """--sys.stream.* and --sys.flight.freshness_samples parse into
    the options the streaming plane consumes, and inconsistent
    combinations fail loudly at parse time (ISSUE 20)."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    dflt = SystemOptions.from_args(p.parse_args([]))
    # all DEFAULT OFF: no plane, zero stream.* names
    assert (dflt.stream_batch, dflt.stream_rate,
            dflt.stream_freshness_slo_ms,
            dflt.stream_freshness_slo_class) == (0, 0.0, 0.0, "")
    assert dflt.flight_freshness_samples == 1024
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.stream.batch", "32", "--sys.stream.rate", "2000",
         "--sys.stream.freshness_slo_ms", "400,1=200",
         "--sys.trace.flight", "1",
         "--sys.flight.freshness_samples", "64"]))
    assert on.stream_batch == 32 and on.stream_rate == 2000.0
    # the flag carries "base,prio=ms,..." — split at parse time
    assert on.stream_freshness_slo_ms == 400.0
    assert on.stream_freshness_slo_class == "1=200"
    assert on.flight_freshness_samples == 64
    bad = (["--sys.stream.batch", "-1"],
           ["--sys.stream.rate", "-2"],
           # rate needs a batch to pace
           ["--sys.stream.rate", "100"],
           ["--sys.stream.freshness_slo_ms", "-5"],
           # the controller without its sensor / its registry
           ["--sys.stream.freshness_slo_ms", "50"],
           ["--sys.stream.freshness_slo_ms", "50",
            "--sys.trace.flight", "1", "--sys.metrics", "0"],
           # probe bound floor
           ["--sys.flight.freshness_samples", "4"],
           # per-class semantics: dup class / non-positive target
           ["--sys.stream.freshness_slo_ms", "400,1=200,1=100",
            "--sys.trace.flight", "1"],
           ["--sys.stream.freshness_slo_ms", "400,1=-5",
            "--sys.trace.flight", "1"])
    for argv in bad:
        with pytest.raises(ValueError):
            SystemOptions.from_args(p.parse_args(argv))
    # malformed class SYNTAX is rejected by argparse itself
    with pytest.raises(SystemExit):
        p.parse_args(["--sys.stream.freshness_slo_ms", "400,x=oops"])
    # hand-built options are validated again at plane construction
    with pytest.raises(ValueError, match="stream.rate"):
        SystemOptions(stream_rate=100.0).validate_serve()
    with pytest.raises(ValueError, match="freshness_samples"):
        SystemOptions(flight_freshness_samples=2).validate_serve()


def test_serve_slo_class_spec_round_trip_and_rejection():
    """--sys.serve.slo_ms accepts per-priority-class overrides
    ("20,1=5"); the no-override spec stays byte-identical (ISSUE 20
    satellite)."""
    import argparse

    import pytest

    from adapm_tpu.config import SystemOptions, parse_class_targets
    p = argparse.ArgumentParser()
    SystemOptions.add_arguments(p)
    plain = SystemOptions.from_args(p.parse_args(
        ["--sys.serve.slo_ms", "20"]))
    assert plain.serve_slo_ms == 20.0 and plain.serve_slo_class == ""
    assert parse_class_targets(plain.serve_slo_ms,
                               plain.serve_slo_class) == {}
    on = SystemOptions.from_args(p.parse_args(
        ["--sys.serve.slo_ms", "20,1=5,0=50"]))
    assert on.serve_slo_ms == 20.0 and on.serve_slo_class == "1=5,0=50"
    assert parse_class_targets(on.serve_slo_ms, on.serve_slo_class) \
        == {1: 5.0, 0: 50.0}
    # overrides demand a base target; negative classes are rejected
    with pytest.raises(ValueError):
        parse_class_targets(0.0, "1=5")
    with pytest.raises(ValueError):
        SystemOptions.from_args(p.parse_args(
            ["--sys.serve.slo_ms", "20,-1=5"]))
