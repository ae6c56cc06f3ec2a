"""What a compiled program has to do, worked out from shapes: the
yardstick's operations and bytes, kept with the benchmark so that no later
PR can change them.

A fused step has to move every row it touches three times: one read for
the gather, one read and one write for the scatter-add (the update is
added into the stored row). Rows named twice inside a batch count twice:
the program does not deduplicate them. The arithmetic of a step is a few
operations per byte moved, far below the chip's operations-per-byte, so
the bound is bandwidth and the operations are not counted.
"""
from __future__ import annotations

import json
import os


def fused_step_bytes(rows_per_example: int, batch_size: int,
                     row_bytes: int) -> int:
    """Bytes one fused step has to move through HBM."""
    return batch_size * rows_per_example * row_bytes * 3


def step_shape(cfg: dict) -> dict:
    """rows per example, batch and row bytes of a configuration's step,
    as its file states them under `step` (`selfcheck.py` works both out
    again from the model's own sizes)."""
    return {"rows_per_example": cfg["step"]["rows_per_example"],
            "batch_size": cfg["batch_size"],
            "row_bytes": cfg["step"]["row_bytes"]}


def peaks(device_kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an error,
    never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json ({sorted(table)})")
    return table[device_kind]
