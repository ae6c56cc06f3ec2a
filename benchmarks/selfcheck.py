#!/usr/bin/env python3
"""Checks of the yardstick itself, each against a case worked by hand or
by an independent plain loop: the numpy references, the bytes of a fused
step, the seeded table's hash, the trace reduction (on a synthetic trace
with known answers and on the small recorded v5e trace in `traces/`), and
the consistency of BENCHMARK.json with the files it names, and what a
result line says of its checks.

    python benchmarks/selfcheck.py        # prints one line per check

Needs no accelerator; `benchmarks/tests/test_selfcheck.py` runs the same
functions under pytest.
"""
from __future__ import annotations

import json
import math
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import common  # noqa: E402
import counts  # noqa: E402
import trace_reduce  # noqa: E402
from reference import adagrad_np, complex_np, sgns_np  # noqa: E402


def _close(a, b, tol=1e-6):
    assert np.allclose(a, b, rtol=tol, atol=tol), (a, b)


def check_complex_by_hand():
    """d=1: s=1+2i, r=3+4i, o=5+6i, one negative n=0.5-i."""
    s, r, o = (np.array([x], np.float32) for x in ([1, 2], [3, 4], [5, 6]))
    n = np.array([[[0.5, -1.0]]], np.float32)
    assert complex_np.score(s, r, o)[0] == 35.0
    assert complex_np.score(n[0], r, o)[0] == 21.5
    assert complex_np.score(s, r, n[0])[0] == -12.5
    sp = lambda x: math.log1p(math.exp(x))  # noqa: E731
    loss, g = complex_np.loss_and_grads(s, r, o, n)
    _close(loss, sp(-35) + sp(21.5) + sp(-12.5))
    # d/ds = -sig(-35) d score(s,r,o)/ds + sig(-12.5) d score(s,r,n)/ds
    sg = lambda x: 1 / (1 + math.exp(-x))  # noqa: E731
    ds_pos = np.array([3 * 5 + 4 * 6, 3 * 6 - 4 * 5], float)   # rr*or+ri*oi, rr*oi-ri*or
    ds_neg = np.array([3 * .5 + 4 * -1, 3 * -1 - 4 * .5], float)
    _close(g["s"][0], -sg(-35) * ds_pos + sg(-12.5) * ds_neg)
    # the negative: subject side d/dn score(n,r,o), object side d/dn score(s,r,n)
    dn_s = np.array([3 * 5 + 4 * 6, 3 * 6 - 4 * 5], float)
    dn_o = np.array([1 * 3 - 2 * 4, 2 * 3 + 1 * 4], float)
    _close(g["neg"][0, 0], sg(21.5) * dn_s + sg(-12.5) * dn_o)


def check_complex_by_differences():
    """Gradients of a random case against central differences of a
    plain float64 loop over triples and negatives."""
    rng = np.random.default_rng(3)
    B, N, d = 3, 2, 2
    s, r, o = (rng.normal(size=(B, 2 * d)) for _ in range(3))
    n = rng.normal(size=(B, N, 2 * d))

    def sc(a, b, c):
        tot = 0.0
        for j in range(d):
            x, y = complex(a[j], a[d + j]), complex(b[j], b[d + j])
            tot += (x * y * complex(c[j], -c[d + j])).real
        return tot

    def loss(s, r, o, n):
        tot = 0.0
        for b in range(B):
            tot += math.log1p(math.exp(-sc(s[b], r[b], o[b])))
            for j in range(N):
                tot += math.log1p(math.exp(sc(n[b, j], r[b], o[b])))
                tot += math.log1p(math.exp(sc(s[b], r[b], n[b, j])))
        return tot / B

    got_loss, g = complex_np.loss_and_grads(
        *(x.astype(np.float32) for x in (s, r, o, n)))
    _close(got_loss, loss(s, r, o, n), 1e-5)
    arrs = {"s": s, "r": r, "o": o, "neg": n}
    for name, a in arrs.items():
        it = np.nditer(a, flags=["multi_index"])
        for _ in it:
            i = it.multi_index
            keep = a[i]
            a[i] = keep + 1e-5
            up = loss(s, r, o, n)
            a[i] = keep - 1e-5
            dn = loss(s, r, o, n)
            a[i] = keep
            _close(g[name][i], (up - dn) / 2e-5, 2e-3)


def check_sgns_by_hand():
    """u=(1,2), v=(0.5,-1): u.v=-1.5; one noise word n=(2,0): u.n=2."""
    u = np.array([[1, 2]], np.float32)
    v = np.array([[0.5, -1]], np.float32)
    n = np.array([[[2, 0]]], np.float32)
    loss, g = sgns_np.loss_and_grads(u, v, n)
    _close(loss, 1.7014132779827524 + 2.1269280110429727)
    _close(g["center"][0], [1.352807, 0.817574], 1e-5)
    _close(g["ctx"][0], [-0.817574, -1.635149], 1e-5)
    _close(g["neg"][0, 0], [0.880797, 1.761594], 1e-5)


def check_adagrad_by_hand():
    upd = adagrad_np.position_updates(
        np.array([2e-3], np.float32), np.array([1e-6], np.float32), 0.1)
    _close(upd, [-2e-4 / math.sqrt(1e-6 + 4e-6 + 1e-10), 4e-6], 1e-6)
    st = adagrad_np.RowState(2)
    st.ensure(np.array([7, 3]), lambda ks: np.zeros((len(ks), 2),
                                                    np.float32))
    st.add(np.array([7, 7, 3]), np.array([[1, 2], [10, 20], [5, 5]],
                                         np.float32))
    assert st.get(np.array([7]))[0].tolist() == [11, 22]   # pushes add up
    assert st.get(np.array([3]))[0].tolist() == [5, 5]


def check_counts_by_hand():
    kge = counts.fused_step_bytes(3 + 32, 4096, 8192)
    assert kge == 4096 * 35 * 8192 * 3 == 3_523_215_360
    w2v = counts.fused_step_bytes(2 + 5, 8192, 8192)
    assert w2v == 1_409_286_144
    bw = counts.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    _close(kge / bw * 1e3, 4.30185, 1e-4)     # ms at 819 GB/s
    _close(w2v / bw * 1e3, 1.72074, 1e-4)
    try:
        counts.peaks("no such device")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


def _step_from_model(cfg: dict) -> dict:
    """A step's rows per example and row bytes worked out again from the
    model's own sizes: ComplEx rows are [re d | im d | AdaGrad 2d], an
    example is s, r, o and its negatives; SGNS rows are [vector d |
    AdaGrad d], an example is centre, context and its noise words."""
    if cfg["app"] == "kge" and cfg["model"] == "complex":
        return {"rows_per_example": 3 + cfg["neg_ratio"],
                "row_bytes": 4 * cfg["dim"] * 4}
    if cfg["app"] == "w2v":
        return {"rows_per_example": 2 + cfg["negatives"],
                "row_bytes": 2 * cfg["dim"] * 4}
    return None    # a model this check does not know: its file is its word


def check_config_step_shapes():
    """Every configuration file states its step (`step`), at its real and
    its rehearsal size, and where the model is one known here the
    statement agrees with the model's sizes."""
    names = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "configs"))
                   if f.endswith(".json"))
    assert names
    for name in names:
        cfg = common.load_json("configs", name + ".json")
        for sizes in (cfg, {**cfg, **cfg.get("rehearse_cpu", {})}):
            shape = counts.step_shape(sizes)
            assert counts.fused_step_bytes(**shape) > 0
            want = _step_from_model(sizes)
            assert want is None or want == sizes["step"], (name, want)
    kge = counts.step_shape(common.load_json("configs",
                                             "kge-wikidata5m.json"))
    assert counts.fused_step_bytes(**kge) == 3_523_215_360


def check_table_hash_by_plain_ints():
    """`table_rows` against the same hash in plain Python integers."""
    M = 0xFFFFFFFF

    def mix(x):
        x = (x * 0x9E3779B1) & M
        x ^= x >> 16
        x = (x * 0x85EBCA6B) & M
        x ^= x >> 13
        x = (x * 0xC2B2AE35) & M
        return x ^ (x >> 16)

    seed, scale = 2**32 + 12345, 0.1
    keys = np.array([0, 1, 4_594_484, 123_456])
    rows = common.table_rows(keys, 6, 4, scale, 1e-6, seed)
    assert rows.dtype == np.float32 and rows.shape == (4, 6)
    for i, k in enumerate(keys.tolist()):
        for c in range(4):
            h = mix((mix(k ^ common.seed32(seed)) + c) & M)
            want = np.float32(np.float32((h >> 8) * 2.0 ** -24)
                              - np.float32(0.5)) * np.float32(2 * scale)
            assert rows[i, c] == want, (k, c, rows[i, c], want)
        assert (rows[i, 4:] == np.float32(1e-6)).all()
    assert np.abs(rows[:, :4]).max() <= scale


def check_trace_reduction_synthetic(tmp=None):
    """A trace written by `encode_xspace` with answers known by hand:
    window 0-100 us; device ops at 10-30, 20-40 (overlap) and 60-70 us:
    busy 40 us, idle 60%; one program run of 30 us."""
    import tempfile
    us = 1000.0
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(123)", 10 * us, 30 * us)],
            "XLA Ops": [("fusion.1", 10 * us, 20 * us),
                        ("scatter.2", 20 * us, 20 * us),
                        ("copy.3", 60 * us, 10 * us)]},
        "/host:CPU": {
            "main": [("bench.window", 0.0, 100 * us),
                     ("bench.host_thing", 41 * us, 18 * us)]}}
    with tempfile.TemporaryDirectory(dir=tmp) as d:
        path = os.path.join(d, "t.xplane.pb")
        with open(path, "wb") as f:
            f.write(trace_reduce.encode_xspace(planes))
        red = trace_reduce.reduce_file(path, 1)
    _close(red["window_s"], 100e-6)
    _close(red["busy_s"], 40e-6)
    _close(red["idle_share_worst"], 0.6)
    _close(red["programs"]["jit_step"]["seconds"], 30e-6)
    assert red["programs"]["jit_step"]["count"] == 1
    assert red["device_ops"][0][0] in ("fusion.1", "scatter.2")
    gaps = dict(red["idle_gaps"])
    _close(gaps["bench.host_thing"], 20e-6)       # the 40-60 us gap
    _close(gaps["(no host event)"], 40e-6)        # 0-10 and 70-100 us


def check_trace_reduction_recorded():
    """The recorded v5e traces reduce to the numbers recorded with them."""
    exp = common.load_json("traces", "expected.json")
    for name, want in exp.items():
        red = trace_reduce.reduce_file(os.path.join(HERE, "traces", name), 1)
        _close(red["window_s"], want["window_s"], 1e-9)
        _close(red["busy_s"], want["busy_s"], 1e-9)
        assert 0 < red["busy_s"] <= red["window_s"]
        for prog, w in want["programs"].items():
            _close(red["programs"][prog]["seconds"], w["seconds"], 1e-9)
            assert red["programs"][prog]["count"] == w["count"]


def check_benchmark_json():
    """Every name in BENCHMARK.json leads to its file, and the files
    agree with it."""
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for c in bench["configs"]:
        cfg = common.load_json("configs", c["name"] + ".json")
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert sorted(cfg["reduced"]) == sorted(c["reduced"]), c["name"]
    for cell in bench["workloads"]:
        assert cell["config"] in configs
        tr = common.load_json("traffic", cell["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            HERE, "drivers", tr["driver"] + ".py")), tr["driver"]
    for m in bench["per_layer"]:
        spec = common.load_json("layer_metrics", m["name"] + ".json")
        assert (spec["name"], spec["layer"], spec["moves"]) == \
            (m["name"], m["layer"], m["moves"]), m["name"]
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            HERE, "sources", spec["kind"] + ".py")), spec["kind"]


def check_result_line_says_which_check_failed():
    """`run.with_checks`: a sound run's line ends in `checks` (every
    number beside its limit) and has no `not_ok`; a refused run's ends in
    `not_ok`, the failed checks alone, and lists them last in `checks`;
    a reading that is not finite leaves the line JSON."""
    import io
    import run
    out, common.OUT = common.OUT, io.StringIO()
    try:
        sound, refused = common.Checks(), common.Checks()
        sound.add("a_gap", 1e-7, 3e-7)
        sound.add("b_count", 5, "> 0", ok=True)
        refused.add("a_gap", 4e-7, 3e-7)
        refused.add("b_count", 5, "> 0", ok=True)
        refused.add("c_gap", float("nan"), 1e-6)
    finally:
        common.OUT = out
    line = run.with_checks({"correct": sound.correct}, sound)
    assert list(line) == ["correct", "checks"] and line["correct"] is True
    assert line["checks"] == {"a_gap": [1e-7, 3e-7], "b_count": [5.0, "> 0"]}
    line = json.loads(common.dump(
        run.with_checks({"correct": refused.correct}, refused)))
    assert list(line) == ["correct", "checks", "not_ok"]
    assert line["correct"] is False
    assert line["not_ok"] == {"a_gap": [4e-7, 3e-7], "c_gap": ["nan", 1e-6]}
    assert list(line["checks"]) == ["b_count", "a_gap", "c_gap"]


CHECKS = [check_complex_by_hand, check_complex_by_differences,
          check_sgns_by_hand, check_adagrad_by_hand, check_counts_by_hand,
          check_config_step_shapes,
          check_table_hash_by_plain_ints, check_trace_reduction_synthetic,
          check_trace_reduction_recorded, check_benchmark_json,
          check_result_line_says_which_check_failed]


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    bad = 0
    for fn in CHECKS:
        try:
            fn()
            print(f"ok   {fn.__name__}")
        except Exception as e:  # every check runs; any failure fails
            bad += 1
            print(f"FAIL {fn.__name__}: {type(e).__name__}: {e}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
