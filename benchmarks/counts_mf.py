"""Bytes of the programs the MF configuration adds, worked out from
shapes (see counts.py for the fused step's).

The gather-only score program (`jit_score`: the pass-end loss walk) reads
every row it names once and writes nothing back: two rows an example, the
row factor's and the column factor's. Rows named twice inside a batch
count twice, as in `counts.fused_step_bytes`: the program does not
deduplicate them.
"""
from __future__ import annotations


def score_bytes(batch_size: int, row_bytes: int) -> int:
    """Bytes one score dispatch has to move through HBM."""
    return batch_size * 2 * row_bytes
