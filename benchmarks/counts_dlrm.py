"""Bytes and operations of the DLRM configuration's fused step, worked out
from shapes (see counts.py for the rule).

The step names two kinds of rows: the members' feature rows (members an
example x batch, rows named twice count twice: the program does not
deduplicate them) and ALL rows of the dense network, once. Each is moved
three times, as in `counts.fused_step_bytes`: one read for the gather, one
read and one write for the write-back. The dense network's matrix
products are 2 FLOP a parameter and example forward and 4 backward (the
gradient of the weights and of the layer's input); biases, the
element-wise cross products and the loss are not counted.
"""
from __future__ import annotations


def dense_sizes(cfg: dict) -> dict:
    """Parameters in the dense network's matrices, in all its tensors, and
    the rows of `dense_row` weights that hold them (every tensor, weight
    matrix or bias, padded to whole rows)."""
    d0 = (len(cfg["multi_hot_sizes"]) + 1) * cfg["embedding_dim"]
    mats, vecs = [], []
    for sizes in ([cfg["dense_features"]] + cfg["dense_arch_layer_sizes"],
                  [d0] + cfg["over_arch_layer_sizes"]):
        mats += [a * b for a, b in zip(sizes, sizes[1:])]
        vecs += sizes[1:]
    for _ in range(cfg["dcn_num_layers"]):
        mats += [cfg["dcn_low_rank_dim"] * d0] * 2
        vecs.append(d0)
    row = cfg["dense_row"]
    return {"matrix_params": sum(mats), "params": sum(mats) + sum(vecs),
            "rows": sum(-(-n // row) for n in mats + vecs)}


def step_bytes(cfg: dict) -> int:
    """Bytes one fused step has to move through HBM."""
    member_rows = cfg["step"]["rows_per_example"] * cfg["batch_size"]
    dense_rows = dense_sizes(cfg)["rows"]
    return (member_rows * cfg["step"]["row_bytes"]
            + dense_rows * 2 * cfg["dense_row"] * 4) * 3


def dense_flops(cfg: dict) -> int:
    """Floating-point operations of the dense network's matrix products
    in one step, forward and backward."""
    return 6 * dense_sizes(cfg)["matrix_params"] * cfg["batch_size"]
