"""Native runtime loader: compiles router.cpp once (g++ -O3 -shared) into a
cache directory and binds it with ctypes. `get_lib()` returns None when
the build or load fails — callers keep a numpy path — but never
silently: the failure is reported once on stderr with the compiler's own
output (`ADAPM_NO_NATIVE=1` is the quiet, deliberate way to run without
it).

The reference ships its host runtime as C++ (libadapm.a); here the host-side
hot loops (route resolution per fused step, stat counters, intent/replica
scans) are the native surface, while the device data plane is XLA.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional

import numpy as np

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_SRC = os.path.join(os.path.dirname(__file__), "router.cpp")


def _cache_dir() -> str:
    d = os.environ.get("ADAPM_NATIVE_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "adapm_tpu")
    os.makedirs(d, exist_ok=True)
    return d


def _host_tag() -> str:
    """Cache-key component for the build host's ISA: -march=native output is
    only valid on CPUs with the same feature set (shared cache dirs on NFS
    homes would otherwise serve SIGILL-ing binaries to older machines)."""
    import platform
    parts = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    parts.append(line.split(":", 1)[1].strip())
                    break
    except OSError:
        pass
    return hashlib.sha256(" ".join(parts).encode()).hexdigest()[:8]


def _build() -> str:
    """Path of the compiled library (built on first use). Raises
    RuntimeError carrying the compiler's stderr when it cannot be
    built."""
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(_cache_dir(),
                       f"libadapm_router_{tag}_{_host_tag()}.so")
    if os.path.exists(out):
        return out
    tmp = out + f".tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
           _SRC, "-o", tmp]
    errors = []
    # -march=native can be unsupported in exotic environments: one
    # retry without it
    for attempt in (cmd, [c for c in cmd if c != "-march=native"]):
        try:
            subprocess.run(attempt, check=True, capture_output=True,
                           timeout=120)
        except subprocess.CalledProcessError as e:
            errors.append(f"$ {' '.join(attempt)}\n"
                          f"{e.stderr.decode(errors='replace').strip()}")
        except (OSError, subprocess.TimeoutExpired) as e:
            errors.append(f"$ {' '.join(attempt)}\n{e!r}")
            break  # no compiler at all / hung: the retry cannot help
        else:
            os.replace(tmp, out)  # atomic vs concurrent builders
            return out
    raise RuntimeError("\n".join(errors))


def get_lib() -> Optional[ctypes.CDLL]:
    """The compiled router library, or None if unavailable (reported
    once on stderr unless ADAPM_NO_NATIVE asked for it)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("ADAPM_NO_NATIVE"):
            return None
        path = None
        try:
            path = _build()
            lib = ctypes.CDLL(path)
        except (RuntimeError, OSError) as e:
            if path is not None:
                # stale/incompatible cached binary: drop it so the next
                # process rebuilds
                try:
                    os.remove(path)
                except OSError:
                    pass
            print(f"[adapm native] router library unavailable, host "
                  f"routing falls back to numpy "
                  f"(ADAPM_NO_NATIVE=1 silences this):\n{e}",
                  file=sys.stderr, flush=True)
            return None
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.adapm_route.restype = ctypes.c_int64
        lib.adapm_route.argtypes = [
            i64p, ctypes.c_int64, ctypes.c_int64, i32p, i32p, i32p,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, i32p, i32p,
            i32p, i32p, u8p, u8p]
        lib.adapm_count.restype = ctypes.c_int64
        lib.adapm_count.argtypes = [i64p, u8p, ctypes.c_int64,
                                    ctypes.c_int64, i64p, i64p]
        lib.adapm_intent_max.restype = ctypes.c_int64
        lib.adapm_intent_max.argtypes = [i64p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_int64,
                                         i32p]
        lib.adapm_replica_scan.restype = ctypes.c_int64
        lib.adapm_replica_scan.argtypes = [
            i64p, i32p, ctypes.c_int64, i32p, i64p, ctypes.c_int64, u8p]
        lib.adapm_replica_scan2.restype = None
        lib.adapm_replica_scan2.argtypes = [
            i64p, i32p, ctypes.c_int64, i32p, i64p, ctypes.c_int64, u8p,
            i64p, i64p, i64p, i64p, i64p]
        _lib = lib
        return _lib


def route(lib, keys: np.ndarray, owner: np.ndarray, slot: np.ndarray,
          cache_slot_row: np.ndarray, shard: int, oob: int,
          write_through: bool):
    """ctypes wrapper for adapm_route; returns Server._route's tuple layout
    plus the per-key local mask (for locality stats)."""
    n = len(keys)
    num_keys = len(owner)
    o_sh = np.empty(n, np.int32)
    o_sl = np.empty(n, np.int32)
    c_sh = np.empty(n, np.int32)
    c_sl = np.empty(n, np.int32)
    use_c = np.empty(n, np.uint8)
    local = np.empty(n, np.uint8)
    keys = np.ascontiguousarray(keys, np.int64)
    n_remote = lib.adapm_route(
        keys, n, num_keys, owner, slot, cache_slot_row, shard, oob,
        int(write_through), o_sh, o_sl, c_sh, c_sl, use_c, local)
    if n_remote < 0:
        bad = keys[-(n_remote + 1)]
        raise IndexError(
            f"key {bad} is outside the key range [0, {num_keys})")
    return o_sh, o_sl, c_sh, c_sl, use_c.astype(bool), int(n_remote), local


def replica_scan_partition(lib, keys: np.ndarray, shards: np.ndarray,
                           intent_end: np.ndarray, min_clock: np.ndarray,
                           num_keys: int, cross):
    """ctypes wrapper for adapm_replica_scan2: partition a channel
    snapshot into (keep_local, keep_cross, drop_local, drop_cross)
    index arrays in one native pass. `cross` is a uint8 owner-is-remote
    mask or None (single process)."""
    n = len(keys)
    keys = np.ascontiguousarray(keys, np.int64)
    shards = np.ascontiguousarray(shards, np.int32)
    cross = np.zeros(n, np.uint8) if cross is None \
        else np.ascontiguousarray(cross, np.uint8)
    keep_l = np.empty(n, np.int64)
    keep_x = np.empty(n, np.int64)
    drop_l = np.empty(n, np.int64)
    drop_x = np.empty(n, np.int64)
    counts = np.zeros(4, np.int64)
    lib.adapm_replica_scan2(
        keys, shards, n, np.ascontiguousarray(intent_end.ravel(), np.int32),
        min_clock, num_keys, cross, keep_l, keep_x, drop_l, drop_x, counts)
    return (keep_l[: counts[0]], keep_x[: counts[1]],
            drop_l[: counts[2]], drop_x[: counts[3]])
