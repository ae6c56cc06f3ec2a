"""The DLRM reference (`reference/dlrm_np.py`) against a case worked by
hand and against central differences of a plain float64 model, the step's
bytes and operations by hand, and the new per-layer readers on an empty
`env` (the parent commit: nothing to read, nothing raised) and on
hand-made ones."""
import importlib
import json
import math
import os

import numpy as np
import pytest

import counts_dlrm
from reference import dlrm_np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_loss_and_grads_by_hand():
    """One example, one bag of two members of width 1, one dense feature,
    one layer each: every number below is plain arithmetic."""
    tens = dlrm_np.tensors(1, 1, 1, [1], [1], 1, 1)
    assert [n for n, _, _ in tens] == [
        "bot0.w", "bot0.b", "cross0.v", "cross0.w", "cross0.b", "top0.w",
        "top0.b"]
    feat = np.array([[[0.5]], [[1.5]]], np.float32)          # [M=2, B=1, 1]
    t = {"bot0.w": [[0.5]], "bot0.b": [0.25],
         "cross0.v": [[1.0, -1.0]], "cross0.w": [[2.0], [0.5]],
         "cross0.b": [0.5, 0.125], "top0.w": [[3.0], [2.0]],
         "top0.b": [-1.0]}
    t = {k: np.array(v, np.float32) for k, v in t.items()}
    loss, g_feat, g = dlrm_np.loss_and_grads(
        feat, t, np.array([[2.0]], np.float32), np.array([1.0], np.float32),
        [2], 1, 1, 1)
    p, h = 0.5 + 1.5, max(2.0 * 0.5 + 0.25, 0.0)             # bag, bottom
    x0 = [h, p]                                              # [1.25, 2.0]
    v = x0[0] * 1.0 + x0[1] * -1.0                           # -0.75
    u = [2.0 * v + 0.5, 0.5 * v + 0.125]                     # [-1, -0.25]
    x1 = [x0[i] * u[i] + x0[i] for i in range(2)]            # [0, 1.5]
    z = 3.0 * x1[0] + 2.0 * x1[1] - 1.0                      # 2.0
    assert (x0, v, u, x1, z) == ([1.25, 2.0], -0.75, [-1.0, -0.25],
                                 [0.0, 1.5], 2.0)
    assert loss == pytest.approx(math.log1p(math.exp(z)) - z, rel=1e-6)
    dz = 1.0 / (1.0 + math.exp(-z)) - 1.0
    want = {"top0.w": [[x1[0] * dz], [x1[1] * dz]], "top0.b": [dz]}
    dx1 = [3.0 * dz, 2.0 * dz]
    du = [dx1[i] * x0[i] for i in range(2)]
    dv = du[0] * 2.0 + du[1] * 0.5
    want.update({"cross0.b": du, "cross0.w": [[du[0] * v], [du[1] * v]],
                 "cross0.v": [[dv * x0[0], dv * x0[1]]]})
    dx0 = [dx1[i] * u[i] + dx1[i] + dv * (1.0, -1.0)[i] for i in range(2)]
    want.update({"bot0.w": [[2.0 * dx0[0]]], "bot0.b": [dx0[0]]})
    for name, w in want.items():
        np.testing.assert_allclose(g[name], w, rtol=2e-6, err_msg=name)
    np.testing.assert_allclose(g_feat.ravel(), [dx0[1], dx0[1]], rtol=2e-6)


def _plain_loss(feat, t, x, y, hot, depth):
    """The equations again in float64, example by example."""
    nb, nc, nt = depth
    ends = np.cumsum(hot)
    total = 0.0
    for b in range(feat.shape[1]):
        h = x[b]
        for i in range(nb):
            h = np.maximum(h @ t[f"bot{i}.w"] + t[f"bot{i}.b"], 0.0)
        x0 = np.concatenate([h] + [feat[lo:hi, b].sum(0) for lo, hi in
                                   zip(np.r_[0, ends[:-1]], ends)])
        xl = x0
        for l in range(nc):
            xl = x0 * (t[f"cross{l}.w"] @ (t[f"cross{l}.v"] @ xl)
                       + t[f"cross{l}.b"]) + xl
        h = xl
        for i in range(nt):
            h = h @ t[f"top{i}.w"] + t[f"top{i}.b"]
            if i + 1 < nt:
                h = np.maximum(h, 0.0)
        total += math.log1p(math.exp(h[0])) - y[b] * h[0]
    return total / feat.shape[1]


def test_gradients_by_central_differences():
    rng = np.random.default_rng(4)
    hot, d, nd, B = [2, 1, 3], 2, 3, 4
    depth = (2, 2, 2)
    tens = dlrm_np.tensors(nd, d, len(hot), [3, d], [3, 1], 2, 2)
    feat = rng.normal(size=(sum(hot), B, d)) * 0.5
    t = {n: rng.normal(size=s) * 0.5 for n, s, _ in tens}
    x, y = rng.normal(size=(B, nd)), (rng.random(B) < 0.5).astype(float)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    got, g_feat, g = dlrm_np.loss_and_grads(
        f32(feat), {k: f32(v) for k, v in t.items()}, f32(x), f32(y), hot,
        *depth)
    assert got == pytest.approx(_plain_loss(feat, t, x, y, hot, depth),
                                rel=1e-5)
    for name, a, grad in [("feat", feat, g_feat)] + [
            (n, t[n], g[n]) for n in t]:
        for i in np.ndindex(a.shape):
            keep = a[i]
            a[i] = keep + 1e-5
            up = _plain_loss(feat, t, x, y, hot, depth)
            a[i] = keep - 1e-5
            dn = _plain_loss(feat, t, x, y, hot, depth)
            a[i] = keep
            assert grad[i] == pytest.approx((up - dn) / 2e-5, rel=3e-3,
                                            abs=3e-5), (name, i)


def test_pack_is_unpack_s_inverse_and_pads_with_zeros():
    tens = dlrm_np.tensors(3, 2, 2, [4, 2], [3, 1], 1, 2)
    where, total = dlrm_np.rows_of(tens, 8)
    rows = np.arange(total * 8, dtype=np.float32).reshape(total, 8)
    back = dlrm_np.pack(dlrm_np.unpack(rows, tens, 8), tens, 8)
    used = back != 0
    assert (back[used] == rows[used]).all()
    # bot0.w is 3 x 4 = 12 weights in two rows of 8: four entries of pad
    at, n = where["bot0.w"]
    assert n == 2 and (back[at + 1, 4:] == 0).all()


def test_step_adds_a_bag_s_repeated_member_twice():
    """Two members of one bag name one row: both positions' updates are
    formed from the row as it was, and both land."""
    tens = dlrm_np.tensors(1, 1, 1, [1], [1], 1, 1)
    _, total = dlrm_np.rows_of(tens, 2)
    rng = np.random.default_rng(1)
    dense = np.concatenate([rng.normal(size=(total, 2)),
                            np.full((total, 2), 1e-6)], 1).astype(np.float32)
    feat = np.array([[0.5, 1e-6], [1.5, 1e-6]], np.float32)
    once, twice = feat.copy(), feat.copy()
    args = (np.array([[1.0]], np.float32), np.array([0.0], np.float32),
            tens, 2, [2], 1, 1, 1, 0.1)
    dlrm_np.step(once, dense.copy(), np.array([[0], [1]]), *args)
    dlrm_np.step(twice, dense.copy(), np.array([[0], [0]]), *args)
    # members (row 0, row 0) pool to 1.0 instead of 2.0: another gradient,
    # the same for both positions; the row moved by twice one update
    _, g_feat, _ = dlrm_np.loss_and_grads(
        feat[[0, 0], None, :1], dlrm_np.unpack(dense[:, :2], tens, 2),
        args[0], args[1], [2], 1, 1, 1)
    g = float(g_feat[0, 0, 0])
    assert twice[0, 0] == pytest.approx(
        0.5 - 2 * 0.1 * g / math.sqrt(1e-6 + g * g + 1e-10), rel=1e-6)
    assert twice[0, 1] == pytest.approx(1e-6 + 2 * g * g, rel=1e-6)
    assert (twice[1] == feat[1]).all() and (once[1] != feat[1]).any()


CFG = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                  "dlrm-dcnv2-criteo1tb.json")))


def test_counts_by_hand():
    sizes = counts_dlrm.dense_sizes(CFG)
    # bottom 13-512-256-128, three cross layers of 2 x 512 x 3456, top
    # 3456-1024-1024-512-256-1
    mats = (13 * 512 + 512 * 256 + 256 * 128 + 3 * 2 * 512 * 3456
            + 3456 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256)
    assert sizes == {"matrix_params": mats, "params": 16_044_545,
                     "rows": 15_676}
    assert counts_dlrm.step_bytes(CFG) == \
        (214 * 2048 * 1024 + 15_676 * 8192) * 3 == 1_731_624_960
    assert counts_dlrm.dense_flops(CFG) == 6 * mats * 2048
    # 2.114 ms at 819 GB/s; 1.0 ms at the bfloat16 peak
    assert counts_dlrm.step_bytes(CFG) / 819e9 * 1e3 == \
        pytest.approx(2.1143, rel=1e-4)
    assert counts_dlrm.dense_flops(CFG) / 197e12 * 1e3 == \
        pytest.approx(0.99991, rel=1e-4)
    assert sum(CFG["table_rows"]) == 6_380_781
    assert CFG["table_rows"] == [-(-n // 32)
                                 for n in CFG["source_table_rows"]]
    assert sum(CFG["multi_hot_sizes"]) == \
        CFG["step"]["rows_per_example"] == 214


NEW = ["dlrm_step_roofline", "dense_matmul_device_ms",
       "dense_matmul_peak_share", "intent_keys_per_step"]


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
             "trace": {"programs": {"jit_score": {"seconds": 1.0,
                                                  "count": 2}},
                       "device_ops": [["%fusion.1 fusion f32[2,2]", 1.0]]}}
    assert reader.read(empty, spec["args"]) is None
    assert reader.read(dict(empty, trace=None), spec["args"]) is None


def _env():
    class Ctx:
        cfg = CFG
    return {"ctx": Ctx, "device": {"kind": "TPU v5 lite"},
            "res": {"matmul_ops": ["%fusion.7", "%convolution_add_fusion"]},
            "trace": {"programs": {"jit_step(1)": {"seconds": 5.0,
                                                   "count": 100}},
                      "device_ops": [
                          ["%fusion.7 fusion f32[2048,512] <- f32[8,8]", 0.6],
                          ["%convolution_add_fusion fusion f32[8,8]", 0.4],
                          ["%fusion.70 fusion f32[2048,512]", 9.0]]}}


def test_named_op_time_and_the_two_shares_by_hand():
    from sources import roofline_dlrm, trace_named_op_time
    args = {"names": "matmul_ops", "per_program": "^jit_step"}
    # 1.0 s in the two named operations over 100 runs; %fusion.70 is not
    # %fusion.7
    assert trace_named_op_time.read(_env(), args) == pytest.approx(10.0)
    # 0.99991 ms at the peak over 10 ms
    assert roofline_dlrm.read(_env(), dict(args, of="dense_flops")) == \
        pytest.approx(9.9991, rel=1e-4)
    # 2.1143 ms of bytes over 50 ms a step
    assert roofline_dlrm.read(
        _env(), {"of": "step_bytes", "program": "^jit_step"}) == \
        pytest.approx(4.2286, rel=1e-4)


def test_matmul_ops_are_read_off_a_compiled_text():
    """`_ctr.matmul_ops_of` on a cut of a compiled step's text: the
    fusion that calls a computation with a convolution inside, a bare
    dot, and nothing else."""
    from drivers import _ctr
    text = """HloModule jit_step

%fused_computation.61 (p0: f32[8,4], p1: f32[4,2]) -> f32[8,2] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[4,2]{1,0} parameter(1)
  ROOT %convolution.1 = f32[8,2]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf
}

%fused_computation.62 (p0: f32[8,2]) -> f32[8,2] {
  %p0.1 = f32[8,2]{1,0} parameter(0)
  ROOT %add.1 = f32[8,2]{1,0} add(%p0.1, %p0.1)
}

ENTRY %main (a: f32[8,4], b: f32[4,2]) -> f32[8,2] {
  %a = f32[8,4]{1,0} parameter(0)
  %b = f32[4,2]{1,0} parameter(1)
  %fusion.7 = f32[8,2]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.61, metadata={op_name="x"}
  %fusion.70 = f32[8,2]{1,0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.62
  %dot.3 = f32[8,2]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %add.9 = f32[8,2]{1,0} add(%fusion.70, %dot.3)
}
"""
    assert _ctr.matmul_ops_of(text) == ["%dot.3", "%fusion.7"]
