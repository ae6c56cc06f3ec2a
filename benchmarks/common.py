"""What every driver of the benchmark shares: the refusal of anything but a
TPU, the compile counter, the memory read, the seeded table (filled on the
device, reproducible row by row in numpy), the seeded key distributions
and the percentile.

Copied from the program where the program had a sound piece
(`chip_smoke.py`: the refusal, `_Compiles`, the memory read;
`scripts/northstar.py`: the whole-pool device fill), so that no later PR
can change the yardstick; the originals are listed for deletion in
PERF.md's Open questions.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def say(msg: str) -> None:
    """Progress goes to stderr; stdout carries the compared numbers and
    the result line only."""
    print(f"[bench +{time.perf_counter() - T0:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.perf_counter()
# the process's real stdout: `run.py` points sys.stdout at stderr while the
# program runs (its log lines go there), and writes results here
OUT = sys.stdout


# ----------------------------------------------------------------- device

class NoAccelerator(RuntimeError):
    pass


def device_info(chips: int, rehearse: bool) -> dict:
    """The devices this cell runs on, as jax reports them. Anything but
    `chips` TPU devices (CPU devices in a rehearsal) is an error: a
    measurement path never falls back."""
    import jax
    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want or len(devs) < chips:
        raise NoAccelerator(
            f"need {chips} x {want}; jax reports {len(devs)} x "
            f"{devs[0].platform} ({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the first `chips` devices
    (None where the backend reports nothing: the CPU)."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Compiles:
    """Every backend compile request jax makes, from jax.monitoring:
    (time, program name, seconds). A persistent-cache hit is still an
    event (its seconds are the retrieval); hits are counted too."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon
        self.events = []
        self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == self.EVENT:
            self.events.append((time.perf_counter(),
                                kw.get("fun_name", "?"), secs))

    def _event(self, event, **kw):
        if event == self.HIT:
            self.hits += 1

    def between(self, t0: float, t1: float):
        return [e for e in self.events if t0 < e[0] <= t1]


# ------------------------------------------------------ the seeded table

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35


def seed32(seed: int) -> int:
    """Any whole-number seed (the driver's pass 2**31) folded to 32 bits."""
    seed = int(seed)
    return (seed ^ (seed >> 32) ^ 0x5BD1E995) & 0xFFFFFFFF


def app_seed(seed: int) -> int:
    """A 31-bit seed for the program (its --seed feeds
    jax.random.PRNGKey)."""
    return seed32(seed) >> 1


def _mix(x, xp):
    """murmur3's 32-bit finalizer over uint32 arrays of numpy or jax.numpy
    (`xp`): integer arithmetic only, so both give the same bits."""
    u = xp.uint32
    x = x * u(_M1)
    x = x ^ (x >> u(16))
    x = x * u(_M2)
    x = x ^ (x >> u(13))
    x = x * u(_M3)
    return x ^ (x >> u(16))


def _embedding(keys, emb_cols: int, scale: float, seed: int, xp):
    """[..., emb_cols] float32 uniform in [-scale, scale) from a hash of
    (seed, key, column). One convert, one subtract and one multiply in
    float32, each exactly rounded, so numpy and XLA agree bitwise."""
    u = xp.uint32
    k = xp.asarray(keys).astype(xp.uint32)[..., None]
    col = xp.arange(emb_cols, dtype=xp.uint32)
    h = _mix(_mix(k ^ u(seed32(seed)), xp) + col, xp)
    unit = (h >> u(8)).astype(xp.float32) * xp.float32(2.0 ** -24)
    return (unit - xp.float32(0.5)) * xp.float32(2.0 * scale)


def table_rows(keys, row_len: int, emb_cols: int, scale: float,
               acc_init: float, seed: int, xp=np):
    """Row of every key in `keys` ([...] ints, -1 = no key): embedding
    columns from `_embedding`, optimizer-state columns `acc_init`. The
    reference (numpy) and the device fill (XLA) agree bitwise;
    `selfcheck.py` holds a hand-worked row, and every chip run compares
    sampled rows. numpy works a block of keys at a time, so that the
    hash's temporaries stay in the cache."""
    if xp is not np:
        emb = _embedding(keys, emb_cols, scale, seed, xp)
        acc = xp.full(emb.shape[:-1] + (row_len - emb_cols,),
                      xp.float32(acc_init), dtype=xp.float32)
        return xp.concatenate([emb, acc], axis=-1)
    keys = np.asarray(keys)
    flat = keys.reshape(-1)
    out = np.empty((flat.size, row_len), dtype=np.float32)
    out[:, emb_cols:] = np.float32(acc_init)
    for lo in range(0, flat.size, _HASH_BLOCK):
        out[lo:lo + _HASH_BLOCK, :emb_cols] = _embedding(
            flat[lo:lo + _HASH_BLOCK], emb_cols, scale, seed, np)
    return out.reshape(keys.shape + (row_len,))


_HASH_BLOCK = 2048


def fill_store_from_seed(srv, cid: int, keys_of_class: np.ndarray,
                         emb_cols: int, scale: float, acc_init: float,
                         seed: int, slab_f32: int = 1 << 25) -> None:
    """Fill one length class's whole main pool on the device, slab by
    slab, with `table_rows` of the key that lives in each slot (the
    program's addressbook says where). No host copy of the table and no
    `Set` of 9 GB: milliseconds instead of minutes
    (`scripts/northstar.py bulk_device_init`, made reproducible by key)."""
    import jax
    import jax.numpy as jnp
    store = srv.stores[cid]
    S, M, L = store.main.shape
    slot_key = np.full((S, M), -1, dtype=np.int32)
    slot_key[srv.ab.owner[keys_of_class], srv.ab.slot[keys_of_class]] = \
        keys_of_class
    sharding = store.main.sharding
    slot_key = jax.device_put(slot_key, sharding)
    slab = min(max(1, slab_f32 // L), M)   # 128 MiB of rows a call

    def fill(main, slot_key, lo):
        ks = jax.lax.dynamic_slice(slot_key, (0, lo), (S, slab))
        rows = table_rows(ks, L, emb_cols, scale, acc_init, seed, xp=jnp)
        rows = jnp.where((ks >= 0)[..., None], rows, 0).astype(main.dtype)
        return jax.lax.dynamic_update_slice(main, rows, (0, lo, 0))

    fill = jax.jit(fill, donate_argnums=0, out_shardings=sharding)
    lo = 0
    while lo < M:
        # the last slab is moved back to end at M (rows written twice
        # get the same values)
        store.main = fill(store.main, slot_key, jnp.int32(min(lo, M - slab)))
        lo += slab
    jax.block_until_ready(store.main)


def read_rows(srv, keys: np.ndarray, cols: slice = None,
              chunk: int = 8192) -> np.ndarray:
    """Columns `cols` (all, if None) of the main copies of `keys`, through
    `Server.read_main` a chunk at a time: from 65,536 keys on it copies
    the whole pool to the host, and a small chunk's buffers are reused
    where a large one's are fresh memory each time. The last chunk is
    filled up with its last key, so one gather shape serves all."""
    L = int(srv.value_lengths[keys[0]])
    cols = cols or slice(0, L)
    out = np.empty((len(keys), cols.stop - cols.start), dtype=np.float32)
    for lo in range(0, len(keys), chunk):
        ks = keys[lo:lo + chunk]
        n = len(ks)
        if n < chunk < len(keys):
            ks = np.concatenate([ks, np.repeat(ks[-1:], chunk - n)])
        rows = np.asarray(srv.read_main(ks)).reshape(len(ks), L)
        out[lo:lo + n] = rows[:n, cols]
    return out


# -------------------------------------------------- seeded key streams

class Zipf:
    """Zipf(exponent) popularity over `n` ids, drawn by inverse CDF
    (vectorised); rank r is id r, or with `perm_rng` the id a seeded
    permutation gives it."""

    def __init__(self, n: int, exponent: float, perm_rng=None):
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** exponent
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]
        self.perm = None if perm_rng is None else \
            perm_rng.permutation(n).astype(np.int64)

    def draw(self, rng: np.random.Generator, size) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(size), side="right")
        r = np.minimum(r, len(self.cdf) - 1).astype(np.int64)
        return r if self.perm is None else self.perm[r]


def vose_alias(p: np.ndarray):
    """Vose alias table (prob float32[V], alias int32[V]) of the
    distribution p: two uniform draws sample it (a copy of the method of
    `models/sgns.py build_alias_table`, kept here with the traffic)."""
    V = len(p)
    scaled = (np.asarray(p, dtype=np.float64) / np.sum(p) * V).tolist()
    prob, alias = [1.0] * V, [0] * V
    small = [i for i, x in enumerate(scaled) if x < 1.0]
    large = [i for i, x in enumerate(scaled) if x >= 1.0]
    while small and large:
        s, l = small.pop(), large.pop()
        prob[s], alias[s] = scaled[s], l
        scaled[l] = (scaled[l] + scaled[s]) - 1.0
        (small if scaled[l] < 1.0 else large).append(l)
    return (np.asarray(prob, dtype=np.float32),
            np.asarray(alias, dtype=np.int32))


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per (seed, named stream)."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) & (2**63 - 1), tag])


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty sequence."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


# ------------------------------------------------------------- checks

class Checks:
    """The numbers `correct` is decided by, each printed beside its
    limit."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value, limit, ok=None) -> None:
        """`value <= limit` decides unless `ok` is given."""
        value = None if value is None else float(value)
        if ok is None:
            ok = bool(value is not None and np.isfinite(value)
                      and value <= limit)
        self.rows.append((name, value, limit, bool(ok)))
        print(self.line(*self.rows[-1]), file=OUT, flush=True)

    @staticmethod
    def line(name, value, limit, ok) -> str:
        return (f"check {name}: value={value!r} limit={limit!r} "
                f"{'ok' if ok else 'NOT OK'}")

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)

    def failed_last(self) -> list:
        """The rows in the order added, those that failed after the
        others: the end of what a run prints is what a record keeps."""
        return sorted(self.rows, key=lambda r: not r[3])


def dump(obj) -> str:
    return json.dumps(obj, separators=(", ", ": "))
