"""Running one cell's CPU rehearsal in a child process and reading its
result line."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))



def _traffic(cell: dict) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        return json.load(f)


with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    _ONE_CHIP = [c for c in json.load(f)["workloads"] if c["chips"] == 1]
CELLS = [c["name"] for c in _ONE_CHIP]
# the training cells: those whose traffic carries the probe's limits
TRAIN_CELLS = [c["name"] for c in _ONE_CHIP if "limits" in _traffic(c)]


def rehearse(cell: str, *extra: str, script: str = "benchmarks/run.py"):
    """(exit code, result dict, the `check` lines)."""
    p = subprocess.run(
        [sys.executable, script, *extra, "--workload", cell, "--seed",
         "4294967311", "--seconds", "1", "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    assert lines, p.stderr[-3000:]
    assert all(ln.startswith("platform=cpu | ") for ln in lines), lines
    result = json.loads(lines[-1].split(" | ", 1)[1])
    return p.returncode, result, [ln for ln in lines if " check " in ln]
