"""XLA environment helpers for the harness: the CPU-mesh XLA_FLAGS value
and the device-backend pre-check.

XLA's flag parser ABORTS the whole process (parse_flags_from_env.cc
SIGABRT, not a Python exception) when XLA_FLAGS contains a flag the
installed jaxlib does not know, so harness code never hand-writes the
flag string: `mesh_flags(n)` is the one place that spells it, for the
one installation this repo targets (jax/jaxlib 0.9.0, which accepts all
three flags).
"""
from __future__ import annotations

import os
import subprocess
import sys


class AcceleratorUnavailableError(RuntimeError):
    """An accelerator backend cannot be used in this environment —
    NAMED (ISSUE 14 satellite): the TPU path can die AT SETUP (client
    construction aborts / hangs before the first program). Nothing
    degrades to another backend."""


def probe_device_backend(platform=None, timeout: float = 180.0):
    """Can `platform` (None = the environment's default backend)
    initialize and enumerate devices? Probed in a throwaway subprocess
    — an unusable backend often ABORTS or wedges client construction,
    which no in-process try/except survives. The child has exited by
    the time this returns, so it never holds the chip against the
    caller's next process.

    Returns (verdict, detail):
      True,  "tpu x4"      — usable; detail names platform + count
      False, "...rc=134.." — definitively unusable (died at setup)
      None,  "...timeout"  — inconclusive (loaded host); treat as
                             unusable for THIS run, but do not record
                             it as a permanent verdict.
    """
    env = dict(os.environ)
    if platform:
        env["JAX_PLATFORMS"] = platform
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; ds = jax.devices(); "
             "print(ds[0].platform, len(ds))"],
            env=env, capture_output=True, timeout=timeout, text=True)
    except subprocess.TimeoutExpired:
        return None, f"backend probe timed out after {timeout:.0f}s"
    except Exception as e:  # pragma: no cover - spawn failure
        return None, f"backend probe failed to spawn: {e}"
    if r.returncode != 0:
        tail = " | ".join((r.stderr or "").strip().splitlines()[-3:])
        return False, (f"backend died at setup (rc={r.returncode}): "
                       f"{tail or 'no stderr'}")
    parts = r.stdout.split()
    detail = f"{parts[0]} x{parts[1]}" if len(parts) >= 2 else "ok"
    return True, detail


def require_device_backend(platform=None, timeout: float = 180.0) -> str:
    """Raise AcceleratorUnavailableError unless `platform` probes
    usable; returns the probe detail on success. The setup-death guard
    for scripts that would otherwise die mid-construction."""
    verdict, detail = probe_device_backend(platform, timeout=timeout)
    if verdict is not True:
        raise AcceleratorUnavailableError(
            f"accelerator backend "
            f"{platform or os.environ.get('JAX_PLATFORMS', 'default')!r}"
            f" is unusable here: {detail}")
    return detail


def mesh_flags(devices: int) -> str:
    """The harness's XLA_FLAGS value for an N-virtual-device CPU mesh:
    the device-count flag plus the in-process collective watchdog
    timeouts (XLA CPU kills the process after 40 s if rendezvous
    participants straggle, which N participants serialized on a 1-2
    core host legitimately do on big programs)."""
    return (f"--xla_force_host_platform_device_count={devices} "
            "--xla_cpu_collective_call_warn_stuck_timeout_seconds=120 "
            "--xla_cpu_collective_call_terminate_timeout_seconds=900")
