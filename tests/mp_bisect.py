"""Diagnostic harness (not collected by pytest): harsher version of the
stress scenario with subsystem toggles, used to corner rare cross-process
exactness bugs. argv: [mode] where mode in
{full, nointent, repl_only, reloc_only, nopull}."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from xla_compat import mesh_flags  # noqa: E402

os.environ.setdefault("XLA_FLAGS", mesh_flags(2))
os.environ.pop("PYTHONPATH", None)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import threading  # noqa: E402

import numpy as np  # noqa: E402

import adapm_tpu  # noqa: E402
from adapm_tpu.base import MgmtTechniques  # noqa: E402
from adapm_tpu.config import SystemOptions  # noqa: E402
from adapm_tpu.parallel import control  # noqa: E402

mode = sys.argv[1]
K = 32
tech = {"repl_only": MgmtTechniques.REPLICATION_ONLY,
        "reloc_only": MgmtTechniques.RELOCATION_ONLY}.get(
            mode, MgmtTechniques.ALL)
srv = adapm_tpu.setup(K, 2, opts=SystemOptions(
    sync_max_per_sec=1000, techniques=tech))
srv.start_sync_thread()
rank = control.process_id()
ws = [srv.make_worker(i) for i in range(2)]
counts = np.zeros(K, dtype=np.float64)
counts_lock = threading.Lock()
errs = []


def work(wi):
    w = ws[wi]
    rng = np.random.default_rng(1000 * rank + wi)
    try:
        for i in range(60):
            keys = np.unique((K * rng.random(5) ** 2).astype(np.int64))
            if mode != "nointent" and rng.random() < 0.6:
                w.intent(keys, w.current_clock, w.current_clock + 2)
            ts = w.push(keys, np.ones((len(keys), 2), np.float32))
            w.wait(ts)
            with counts_lock:
                counts[keys] += 1
            if mode != "nopull" and rng.random() < 0.4:
                w.pull_sync(keys)
            w.advance_clock()
    except Exception as e:  # noqa: BLE001
        import traceback
        errs.append(traceback.format_exc())
        errs.append(e)


threads = [threading.Thread(target=work, args=(wi,)) for wi in (0, 1)]
for t in threads:
    t.start()
for t in threads:
    t.join()
assert not errs, errs
for w in ws:
    w.wait_all()
srv.wait_sync()
srv.barrier()
srv.wait_sync()
srv.barrier()
total = control.allreduce(counts, "sum")
final = srv.read_main(np.arange(K)).reshape(K, 2)
diff = final[:, 0] - total
if srv._dbg_applies is not None:
    applies = control.allreduce(srv._dbg_applies, "sum")
    adiff = applies - total
    bad = np.nonzero(np.abs(adiff) > 1e-3)[0]
    sent = control.allreduce(srv.glob._dbg["sent"], "sum")
    served = control.allreduce(srv.glob._dbg["served"], "sum")
    print(f"rank={rank} apply-layer diff at {bad.tolist()}: "
          f"{adiff[bad].tolist()} sent={sent[bad].tolist()} "
          f"served={served[bad].tolist()} "
          f"local_direct={(applies - served)[bad].tolist()}", flush=True)
if not np.allclose(final, total[:, None], atol=1e-3):
    print(f"BISECT-FAIL rank={rank} mode={mode} diff={diff.tolist()}",
          flush=True)
    srv.barrier()
    srv.shutdown()
    sys.exit(1)
srv.barrier()
srv.shutdown()
print(f"BISECT-OK rank={rank} mode={mode}")
