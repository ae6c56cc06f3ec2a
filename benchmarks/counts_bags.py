"""Bytes of the program the serving configuration adds, worked out from
what was asked for (see counts.py for the fused step's).

A pooled bag read has to read every member's row once and write every
bag's pooled vector once, and to know which row each member is and
where each bag starts: a 4 B index a member and a 4 B offset a bag.
Members named twice count twice (a bag sums a repeated member twice).
The members and bags are those the requests asked for, not the buckets
the program pads them to, so the count is of the work and not of one
implementation of it. One addition a float read: far below the chip's
operations per byte, so the bound is bandwidth.
"""
from __future__ import annotations


def bag_read_bytes(members: float, bags: float, row_bytes: int) -> float:
    """Bytes a read of `members` member rows pooled into `bags` vectors
    has to move through HBM."""
    return (members + bags) * (row_bytes + 4)
