"""Fault-injection plane + robustness layers (ISSUE 10 tentpole).

Three coupled layers over one seeded injection substrate
(docs/failure_handling.md has the operator guide):

  - `inject`  — `FaultPlane`: deterministic, seeded, named injection
    points threaded through the executor, sync rounds, tier promotion
    commits, serve drains, and checkpoint I/O. Off by default with
    zero hot-path cost (`Server.fault` is None; one `is None` check
    per instrumented site, zero `fault.*` registry names).
  - `policy`  — `RetryPolicy`: transient-vs-fatal classification with
    bounded retry + exponential backoff for executor programs; the
    watchdog half (`AsyncExecutor.wedged_streams`) marks a stream
    wedged past `--sys.fault.watchdog_s` and escalates into serve
    readiness.
  - `ckpt`    — incremental dirty-slot checkpoint chains
    (`IncrementalCheckpointer` / `restore_chain`): base + deltas of
    only the slots whose write epoch advanced, atomic writes,
    per-link sha256 and a chained manifest; restore verifies the
    whole chain before touching the server and serves DEGRADED
    (`ServeDegradedError` sheds) while it applies — never a torn or
    half-restored read.

Drilled end to end by scripts/fault_drill_check.py (run_tests.sh).
"""
from .ckpt import (CheckpointChainError,  # noqa: F401
                   CheckpointCorruptError, IncrementalCheckpointer,
                   restore_chain)
from .inject import (FatalInjectedFault, FaultPlane,  # noqa: F401
                     InjectedFault, TransientFaultError,
                     parse_fault_spec)
from .policy import RetryPolicy  # noqa: F401
