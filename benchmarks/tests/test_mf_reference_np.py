"""The MF reference (`reference/mf_np.py`) against cases worked by hand
and against central differences of a plain float64 loop, the bytes of the
score program by hand, and the new per-layer readers on an empty `env`
(the parent commit: nothing to read, nothing raised)."""
import importlib
import json
import math
import os

import numpy as np
import pytest

import counts_mf
from reference import mf_np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_loss_and_grads_by_hand():
    """w=(1,2), h=(0.5,-1), x=0.5, l2=0.1: <w,h> = -1.5, residual -2;
    loss = 4 + 0.1 (5 + 1.25) = 4.625; d/dw = 2(-2)h + 0.2 w,
    d/dh = 2(-2)w + 0.2 h."""
    w = np.array([[1, 2]], np.float32)
    h = np.array([[0.5, -1]], np.float32)
    loss, g = mf_np.loss_and_grads(w, h, np.array([0.5], np.float32), 0.1)
    assert loss == pytest.approx(4.625, rel=1e-6)
    np.testing.assert_allclose(g["w"][0], [-1.8, 4.4], rtol=1e-6)
    np.testing.assert_allclose(g["h"][0], [-3.9, -8.2], rtol=1e-6)
    # a block of a batch of 4 gives its share of the batch's mean
    part, gp = mf_np.loss_and_grads(w, h, np.array([0.5], np.float32), 0.1,
                                    batch_size=4)
    assert part == pytest.approx(4.625 / 4, rel=1e-6)
    np.testing.assert_allclose(gp["w"], g["w"] / 4, rtol=1e-6)


def test_gradients_by_central_differences():
    rng = np.random.default_rng(3)
    B, d, l2 = 5, 3, 0.05
    w, h = rng.normal(size=(B, d)), rng.normal(size=(B, d))
    x = rng.normal(size=B)

    def loss(w, h):
        tot = 0.0
        for b in range(B):
            pred = sum(w[b, k] * h[b, k] for k in range(d))
            tot += (pred - x[b]) ** 2 + l2 * sum(
                w[b, k] ** 2 + h[b, k] ** 2 for k in range(d))
        return tot / B

    got, g = mf_np.loss_and_grads(w.astype(np.float32),
                                  h.astype(np.float32),
                                  x.astype(np.float32), l2)
    assert got == pytest.approx(loss(w, h), rel=1e-5)
    for name, a in (("w", w), ("h", h)):
        for i in np.ndindex(a.shape):
            keep = a[i]
            a[i] = keep + 1e-5
            up = loss(w, h)
            a[i] = keep - 1e-5
            dn = loss(w, h)
            a[i] = keep
            assert g[name][i] == pytest.approx((up - dn) / 2e-5,
                                               rel=2e-3, abs=2e-5)


def test_step_adds_duplicates_up_from_the_rows_before_the_step():
    """Two positions name column key 2: both updates are formed from the
    row as it was, and both land."""
    rank, lr, l2 = 1, 0.1, 0.0
    table = np.array([[1.0, 1e-6], [2.0, 1e-6], [0.5, 1e-6]], np.float32)
    want = table.copy()
    wk, hk = np.array([0, 1]), np.array([2, 2])
    x = np.array([0.0, 0.0], np.float32)
    loss = mf_np.step(table, wk, hk, x, l2, lr)
    assert loss == pytest.approx((0.25 + 1.0) / 2)
    # residuals 0.5 and 1.0; dL/dh per position = (2 res / B) w
    for res, wv in ((0.5, 1.0), (1.0, 2.0)):
        g = res * wv
        want[2, 0] += -lr * g / math.sqrt(1e-6 + g * g + 1e-10)
        want[2, 1] += g * g
    np.testing.assert_allclose(table[2], want[2], rtol=1e-6)
    g0 = 0.5 * 0.5
    assert table[0, 0] == pytest.approx(
        1.0 - lr * g0 / math.sqrt(1e-6 + g0 * g0 + 1e-10), rel=1e-6)


def test_full_loss_by_hand():
    W = np.array([[1, 0], [0, 2]], np.float32)
    H = np.array([[3, 1], [1, 1]], np.float32)
    rows, cols = np.array([0, 1, 1]), np.array([0, 0, 1])
    vals = np.array([1.0, 0.0, 2.0], np.float32)
    # predictions 3, 2, 2: squared errors 4 + 4 + 0; |W|^2 = 5, |H|^2 = 12
    assert mf_np.full_loss(rows, cols, vals, W, H, 0.5) == \
        pytest.approx(8 + 0.5 * 17)


def test_score_bytes_by_hand():
    assert counts_mf.score_bytes(8192, 8192) == 134_217_728
    # 0.164 ms at 819 GB/s
    assert counts_mf.score_bytes(8192, 8192) / 819e9 * 1e3 == \
        pytest.approx(0.16388, rel=1e-4)


NEW = ["batch_unique_key_share", "loss_pass_ms", "score_device_ms",
       "score_roofline", "fused_scan_device_ms"]


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_nothing_from_a_program_without_it(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[name]
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert (spec["name"], spec["layer"], spec["moves"]) == \
        (name, entry["layer"], entry["moves"])
    reader = importlib.import_module("sources." + spec["kind"])
    empty = {"obs0": {}, "obs1": {}, "res": {}, "device": {}, "ctx": None,
             "trace": {"programs": {"jit_step": {"seconds": 1.0,
                                                 "count": 2}}}}
    assert reader.read(empty, spec["args"]) is None


def test_score_roofline_by_hand():
    from sources import roofline_score

    class Ctx:
        cfg = {"batch_size": 8192, "step": {"row_bytes": 8192}}
    env = {"ctx": Ctx, "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {"jit_score(1)": {"seconds": 0.5,
                                                   "count": 1000}}}}
    # 0.16388 ms of bytes over 0.5 ms a run
    assert roofline_score.read(env, {"program": "^jit_score"}) == \
        pytest.approx(32.776, rel=1e-3)
