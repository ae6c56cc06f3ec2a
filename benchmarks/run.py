#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json; everything that
belongs to it is found by name: its configuration in
`benchmarks/configs/<config>.json`, its traffic in
`benchmarks/traffic/<traffic>.json`, the driver the traffic names in
`benchmarks/drivers/<driver>.py`, and each per-layer metric in
`benchmarks/layer_metrics/<metric>.json`, read by
`benchmarks/sources/<kind>.py`. See benchmarks/README.md.

The last line of stdout is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, `breakdown` in a traced run, then `checks`:
every number compared beside its limit, and `not_ok`, the failed ones,
where `correct` is false). With
anything but the TPUs the cell asks for, the exit code is 2 and no result
is printed. `--rehearse-cpu` is the explicit CPU rehearsal at tiny sizes:
every line is labelled `platform=cpu`, it prints no metric under a device
metric's name, and it is never a fallback.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import common  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class Ctx:
    """What a driver and a per-layer reader get to see of the run."""

    def __init__(self, args, bench, cell):
        self.bench = bench
        self.cell = cell
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse_cpu
        self.control = args.control
        self.sweep_rates = [float(x) for x in args.sweep_rates.split(",")] \
            if args.sweep_rates else []
        self.cfg = common.load_json("configs", cell["config"] + ".json")
        self.traffic = common.load_json("traffic", cell["traffic"] + ".json")
        if self.rehearse:
            # the tiny sizes of the rehearsal live beside the real ones
            self.cfg.update(self.cfg.get("rehearse_cpu", {}))
            self.traffic.update(self.traffic.get("rehearse_cpu", {}))
        self.checks = common.Checks()
        self.compiles = None
        self.program_lines = []   # what the program printed


class _Lines(io.TextIOBase):
    """Line-buffered pass-through that labels every line (the rehearsal's
    `platform=cpu`) and can keep the lines for a driver to read."""

    def __init__(self, dest, label="", keep=None):
        self.dest, self.label, self.keep, self._buf = dest, label, keep, ""

    def write(self, s: str) -> int:
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if self.keep is not None:
                self.keep.append(line)
            self.dest.write(self.label + line + "\n")
        self.dest.flush()
        return len(s)


def _obs(srv) -> dict:
    """Every metric of the program's registry, by name."""
    out = {}
    for name in srv.obs.names():
        m = srv.obs.find(name)
        if m is not None:
            out[name] = m.snap()
    return out


def _layer_metrics(ctx, env) -> dict:
    """Each per-layer metric of this cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in ctx.bench["per_layer"]:
        if "workloads" in m and ctx.cell["name"] not in m["workloads"]:
            continue
        spec = common.load_json("layer_metrics", m["name"] + ".json")
        reader = importlib.import_module("sources." + spec["kind"])
        value = reader.read(env, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def with_checks(result: dict, checks) -> dict:
    """The result line's last keys: `checks`, every number compared
    beside its limit (name -> [value, limit], those that failed last),
    and, only where the run is not correct, `not_ok`: the failed ones
    alone, so that the end of a refused run's line says why."""
    def pair(value, limit):
        # a reading that is not finite as a string: the line stays JSON
        if value is not None and not math.isfinite(value):
            value = repr(value)
        return [value, limit]
    rows = checks.failed_last()
    result["checks"] = {name: pair(value, limit)
                        for name, value, limit, _ in rows}
    failed = {name: result["checks"][name]
              for name, _, _, ok in rows if not ok}
    if failed:
        result["not_ok"] = failed
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; never a measurement")
    ap.add_argument("--control", default="",
                    choices=["", "bf16", "bf16-compute", "ref-bf16"],
                    help="the lower-precision controls, each of which has "
                         "to come out correct=false: bf16 = the program "
                         "with a bfloat16 store; bf16-compute (training "
                         "cells) = the program's step computing its loss "
                         "and gradients in bfloat16 over the float32 "
                         "store; ref-bf16 (training cells) = the reference "
                         "in bfloat16 in the program's place")
    ap.add_argument("--sweep-rates", default="",
                    help="serve cells: extra windows at these rates first")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no cell {args.workload!r} in BENCHMARK.json "
              f"(cells: {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    chips = cell["chips"]
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    try:
        import adapm_tpu  # noqa: F401
        from adapm_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"run.py: the program is not here ({e}); run from the root "
              f"of a checkout", file=sys.stderr)
        return 2
    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache: a fixed
    # path, so every run after a cell's first finds its programs
    cache_dir = enable_compile_cache()
    try:
        device = common.device_info(chips, args.rehearse_cpu)
    except common.NoAccelerator as e:
        print(f"run.py: {e}. The benchmark runs on the chip only; "
              f"--rehearse-cpu is the explicit CPU rehearsal.",
              file=sys.stderr)
        return 2
    import jax

    ctx = Ctx(args, bench, cell)
    ctx.compiles = common.Compiles()
    label = "platform=cpu | " if args.rehearse_cpu else ""
    common.OUT = _Lines(sys.stdout, label) if label else sys.stdout
    program_out = _Lines(sys.stderr, label, keep=ctx.program_lines)
    common.say(f"cell {cell['name']} seed={args.seed} seconds={args.seconds}"
               f" trace={args.trace} on {device['count']} x "
               f"{device['kind']} ({device['platform']}); compile cache "
               f"{cache_dir}")
    if args.control == "bf16":
        # the program's own lower-precision path: Server(dtype=...)
        import functools
        import jax.numpy as jnp
        adapm_tpu.Server = functools.partial(adapm_tpu.Server,
                                             dtype=jnp.bfloat16)
    if args.control == "bf16-compute":
        # the step a later PR would be tempted by: rows cast to bfloat16
        # where the loss reads them, the store left in float32
        import jax.numpy as jnp
        from adapm_tpu.ops import fused
        build = fused._build_device_routed_body

        def build_in_bf16(loss_fn, *a, **kw):
            def loss_in_bf16(embs, aux):
                low = {r: v.astype(jnp.bfloat16) for r, v in embs.items()}
                return loss_fn(low, aux).astype(jnp.float32)
            return build(loss_in_bf16, *a, **kw)
        fused._build_device_routed_body = build_in_bf16
    driver = importlib.import_module("drivers." + ctx.traffic["driver"])
    state = None
    with contextlib.redirect_stdout(program_out):
        try:
            with jax.profiler.TraceAnnotation("bench.setup"):
                state = driver.setup(ctx)
            srv = state["srv"]
            obs0 = _obs(srv)
            if ctx.trace:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            t_window = time.perf_counter()
            setup_s = t_window - T_PROCESS
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    res = driver.window(ctx, state)
            finally:
                if ctx.trace:
                    jax.profiler.stop_trace()
            obs1 = _obs(srv)
            mem = common.memory_peak_bytes(chips)
            common.say(f"set-up {setup_s:.2f} s; window done; checks")
            driver.check(ctx, state, res, ctx.checks)
            if ctx.trace:
                import trace_reduce
                path = trace_reduce.find_xplane(TRACE_DIR)
                traced = trace_reduce.reduce_file(path, chips)
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
        finally:
            if state is not None:
                driver.close(ctx, state)

    device["memory_peak_bytes"] = mem
    breakdown = None
    if ctx.trace:
        metrics = _layer_metrics(ctx, {
            "ctx": ctx, "res": res, "obs0": obs0, "obs1": obs1,
            "trace": traced, "device": device})
        if traced["devices"]:     # none in a CPU rehearsal
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
            breakdown = {"device_ops": traced["device_ops"][:10],
                         "idle_gaps": traced["idle_gaps"][:10]}
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        for name, value in res["metrics"].items():
            if value is not None:
                metrics[name] = {"value": float(value), "unit": units[name]}
    result = {"correct": ctx.checks.correct, "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    if args.rehearse_cpu:
        # a rehearsal's numbers are not device numbers: the names go, the
        # structure stays
        result = {"rehearsal": True, "correct": result["correct"],
                  "attempted": result["attempted"],
                  "failed": result["failed"],
                  "metric_names": sorted(metrics), "device": device}
    # every number compared beside its limit once more, as the last lines
    # of stderr (those that failed last) and at the end of the result line
    print("".join(f"{label}{ctx.checks.line(*row)}\n"
                  for row in ctx.checks.failed_last()),
          end="", file=sys.stderr, flush=True)
    print(common.dump(with_checks(result, ctx.checks)), file=common.OUT,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
