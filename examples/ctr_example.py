"""CTR (click-through-rate) training through the bindings: a factorization
machine over sparse categorical features, the workload shape of the
reference's external PyTorch apps (adapm-pytorch-apps CTR on Criteo;
reference README.md:23, bindings/README.md).

Everything trainable lives in the parameter manager: one key per feature
value across all fields, value row = [w | v(d) | adagrad(1+d)] — linear
weight, FM factor, and optimizer state co-located the way the reference
apps pack AdaGrad next to weights (e.g. apps/matrix_factorization.cc
param_len = 2*rank). The torch side is a plain autograd FM:

  score(x) = sum_i w_i + 0.5 * sum_d [(sum_i v_id)^2 - sum_i v_id^2]

Workers partition the click log (data parallelism over workers), signal
Intent for the NEXT batch's feature keys one clock ahead (the reference
apps' pipelined lookahead), pull the current batch's unique rows, autograd
the logistic loss, and push additive AdaGrad deltas.

After training, the INFERENCE half serves the same model through the
online serving plane (adapm_tpu/serve; docs/SERVING.md): several client
threads score held-out samples, fetching the FM's per-sample feature
SUMS via fused `ServeSession.lookup_bags` reads (one bag per sample
over its FIELDS keys — the DLRM embedding-bag shape) next to a flat
`lookup` for the quadratic term's squared member rows — the end-to-end
train-then-serve shape of a production CTR system — and both reads are
checked bit-identical against each other and against the training-path
pull (the serving plane's consistency contract).

The same task as a FUSED step (no torch, no per-key pull and push: the
tables and a DLRM-DCNv2 dense network in the store, gather -> loss ->
AdaGrad -> write-back in one compiled program) is `adapm_tpu/apps/ctr.py`.

Run: PYTHONPATH=. python examples/ctr_example.py
"""
import threading

import numpy as np
import torch

from adapm_tpu import bindings as adapm

FIELDS = 6            # categorical fields (Criteo has 26)
VOCAB = 50            # feature values per field
DIM = 8               # FM factor dimension
NUM_KEYS = FIELDS * VOCAB
ROW = 2 * (1 + DIM)   # [w | v | acc_w | acc_v]
NUM_WORKERS = 2
BATCH = 64
EPOCHS = 4
SAMPLES = 2048
LR = 0.1
EPS = 1e-8


def make_click_log(rng):
    """Synthetic Criteo-like log: clicks follow a ground-truth FM."""
    w_true = rng.normal(0, 0.5, NUM_KEYS)
    v_true = rng.normal(0, 0.5, (NUM_KEYS, DIM))
    feats = np.stack([rng.integers(0, VOCAB, SAMPLES) + f * VOCAB
                      for f in range(FIELDS)], axis=1)
    inter = 0.5 * ((v_true[feats].sum(1) ** 2
                    - (v_true[feats] ** 2).sum(1)).sum(1))
    score = w_true[feats].sum(1) + inter
    p = 1.0 / (1.0 + np.exp(-score / max(score.std(), 1e-6)))
    clicks = (rng.random(SAMPLES) < p).astype(np.float32)
    return feats.astype(np.int64), clicks


def fm_forward(rows: torch.Tensor, inv: torch.Tensor) -> torch.Tensor:
    """rows: [U, 1+DIM] trainable (w|v) for the batch's unique keys;
    inv: [B, FIELDS] positions into rows."""
    w = rows[:, 0][inv]                       # [B, F]
    v = rows[:, 1:][inv]                      # [B, F, D]
    inter = 0.5 * ((v.sum(1) ** 2 - (v ** 2).sum(1)).sum(1))
    return w.sum(1) + inter


def run_worker(wid, server, feats, clicks, out):
    w = adapm.Worker(wid, server)
    part = np.arange(wid, SAMPLES, NUM_WORKERS)
    losses = []
    for ep in range(EPOCHS):
        for lo in range(0, len(part), BATCH):
            idx = part[lo:lo + BATCH]
            nxt = part[lo + BATCH:lo + 2 * BATCH]
            if len(nxt):  # pipelined lookahead, one clock ahead
                w.intent(np.unique(feats[nxt]), w.current_clock + 1,
                         w.current_clock + 2)
            uniq, inv = np.unique(feats[idx], return_inverse=True)
            buf = torch.zeros(len(uniq), ROW)
            w.pull(uniq, buf)
            rows = buf[:, :1 + DIM].clone().requires_grad_(True)
            acc = buf[:, 1 + DIM:]
            score = fm_forward(rows, torch.from_numpy(
                inv.reshape(len(idx), FIELDS)))
            y = torch.from_numpy(clicks[idx])
            loss = torch.nn.functional.binary_cross_entropy_with_logits(
                score, y)
            loss.backward()
            g = rows.grad
            # additive AdaGrad delta: [-lr*g/sqrt(acc+g^2) | g^2] updates
            # both the weights and the co-located accumulator in one push
            delta = torch.cat(
                [-LR * g / torch.sqrt(acc + g * g + EPS), g * g], dim=1)
            w.push(uniq, delta, asynchronous=True)
            losses.append(loss.item())
            w.advance_clock()
        w.waitall()
        w.barrier()
    out[wid] = losses
    w.finalize()


def serve_inference(server, feats, clicks, n_clients=4, batch=32,
                    samples=256):
    """Serve the trained FM: each client thread scores its share of the
    held-out samples through coalesced lookups (concurrent clients hit
    the same hot feature rows — the micro-batcher deduplicates the
    union), with a generous per-request deadline so an overloaded box
    sheds instead of hanging.

    The FM's linear term and factor sum are BAG reads — each sample is
    one bag over its FIELDS feature keys, and `lookup_bags` returns the
    sum-pooled [sum w | sum v | sum acc] row per sample straight from
    the fused gather+pool program (docs/SERVING.md "Bag reads"), so the
    per-member rows never cross the wire. The quadratic term needs
    sum_i v_i^2 — a sum of SQUARED member rows, which no linear pooling
    can produce — so the squared correction still rides a flat `lookup`
    of the batch's unique keys; that flat read doubles as the
    bit-identity witness: host-pooling it must reproduce the bag read
    exactly (the serve/bags.py contract)."""
    from adapm_tpu.serve import ServePlane
    from adapm_tpu.serve.bags import pool_bags_host

    plane = ServePlane(server._srv)  # knobs from --sys.serve.* defaults
    held = np.arange(samples)
    parts = np.array_split(held, n_clients)
    preds = [None] * n_clients
    rows_seen = [None] * n_clients

    def client(ci):
        sess = plane.session()
        out, seen = [], {}
        for lo in range(0, len(parts[ci]), batch):
            idx = parts[ci][lo:lo + batch]
            fk = feats[idx]                      # [b, FIELDS]
            b = len(idx)
            ks = fk.ravel().astype(np.int64)
            bg = np.arange(0, len(ks) + 1, FIELDS)
            # one bag per sample: sum-pooled [w|v|acc] rows off the wire
            (pooled,) = sess.lookup_bags([ks], [bg], pooling="sum",
                                         deadline_ms=10_000)
            # flat read for the quadratic term's squared member rows
            uniq, inv = np.unique(fk, return_inverse=True)
            inv = inv.reshape(-1)   # numpy >= 2.1 returns fk's 2-D shape
            rows = sess.lookup(uniq, deadline_ms=10_000)
            host = pool_bags_host(rows[inv],
                                  np.repeat(np.arange(b), FIELDS)
                                  .astype(np.int32), b, "sum")
            assert np.array_equal(pooled, host), \
                "bag read diverged from host pool of the flat read"
            sw = pooled[:, 0]                    # sum_i w_i
            sv = pooled[:, 1:1 + DIM]            # sum_i v_i
            v = rows[:, 1:1 + DIM][inv.reshape(b, FIELDS)]
            out.append(sw + 0.5 * ((sv ** 2).sum(1)
                                   - (v ** 2).sum((1, 2))))
            for k, r in zip(uniq, rows):
                seen[int(k)] = r
        preds[ci] = np.concatenate(out)
        rows_seen[ci] = seen

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # the serving plane's consistency contract: every served row is
    # bit-identical to a plain training-path pull of the same key
    wchk = adapm.Worker(0, server)
    for seen in rows_seen:
        keys = np.fromiter(seen, np.int64, len(seen))
        buf = np.zeros((len(keys), ROW), np.float32)
        wchk.pull(keys, buf)
        assert np.array_equal(
            np.stack([seen[int(k)] for k in keys]), buf), \
            "serve lookup diverged from Worker.pull"

    scores = np.concatenate(preds)
    y = clicks[held]
    p = 1.0 / (1.0 + np.exp(-scores))
    logloss = float(-np.mean(y * np.log(p + 1e-9)
                             + (1 - y) * np.log(1 - p + 1e-9)))
    snap = server._srv.metrics_snapshot()["serve"]
    print(f"serve: {len(held)} samples via {n_clients} clients, "
          f"logloss {logloss:.3f}, {snap['batches_total']} coalesced "
          f"batches for {snap['lookups_total']} lookups + "
          f"{snap['bag_lookups_total']} bag lookups "
          f"({snap['bag_pooled_total']} pooled bags, "
          f"{snap['bag_fused_total']} fused), "
          f"ready={bool(snap['ready'])}")
    plane.close()
    return logloss


def main():
    rng = np.random.default_rng(7)
    feats, clicks = make_click_log(rng)
    adapm.setup(NUM_KEYS, NUM_WORKERS)
    server = adapm.Server(ROW, num_keys=NUM_KEYS)
    # init: worker-0-initializes pattern (accumulator floor via Set)
    init = np.zeros((NUM_KEYS, ROW), dtype=np.float32)
    init[:, 1:1 + DIM] = rng.normal(0, 0.05, (NUM_KEYS, DIM))
    init[:, 1 + DIM:] = 1e-6
    w0 = adapm.Worker(0, server)
    w0.begin_setup()
    w0.set(np.arange(NUM_KEYS), init)
    w0.end_setup()
    w0.wait_sync()

    out = [None] * NUM_WORKERS
    threads = [threading.Thread(target=run_worker,
                                args=(i, server, feats, clicks, out))
               for i in range(NUM_WORKERS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    first = float(np.mean(out[0][:4]))
    last = float(np.mean(out[0][-4:]))
    print(f"ctr: logloss {first:.3f} -> {last:.3f}")
    assert last < 0.92 * first, "FM failed to learn the click model"

    # inference half: serve the trained model through the serving plane
    serve_logloss = serve_inference(server, feats, clicks)
    assert serve_logloss < first, \
        "served model scored worse than the untrained baseline"
    print("ctr example PASSED")
    server.shutdown()


if __name__ == "__main__":
    main()
