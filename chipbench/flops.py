"""Operations and bytes the trainer's work needs, from the configuration's
shapes alone (no code of the program)."""
from __future__ import annotations

import math
from typing import List

LANES = 128                  # floats per row of the packed commit buffer
ROW_ALIGN = 8                # rows: the buffer is padded to a whole tile
F32 = 4


def leaf_sizes(model: dict) -> List[int]:
    """Element count of every parameter tensor of the dense block stack
    with tied embeddings: token table, final LayerNorm, and per layer two
    LayerNorms, q/k/v/o projections and the two MLP matrices, with the
    optional q/k/v and MLP biases."""
    d, h, kv, hd, ff = (model["d_model"], model["n_heads"],
                        model["n_kv_heads"], model["head_dim"], model["d_ff"])
    per_layer = [d, d, d * h * hd, d * kv * hd, d * kv * hd, h * hd * d,
                 d, d, d * ff, ff * d]
    if model.get("qkv_bias"):
        per_layer += [h * hd, kv * hd, kv * hd]
    if model.get("mlp_bias"):
        per_layer += [ff, d]
    return [model["vocab_size"] * d, d, d] + per_layer * model["n_layers"]


def n_params(model: dict) -> int:
    return sum(leaf_sizes(model))


def matmul_params(model: dict) -> int:
    """Weights that enter a matrix product, the tied output head once."""
    d, h, kv, hd, ff = (model["d_model"], model["n_heads"],
                        model["n_kv_heads"], model["head_dim"], model["d_ff"])
    layer = d * h * hd * 2 + d * kv * hd * 2 + 2 * d * ff
    return model["n_layers"] * layer + model["vocab_size"] * d


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward matmul operations per trained token: 6 per
    matmul weight, plus 12 * layers * heads * head_dim * seq for the
    attention scores and their weighted sum (PaLM's model-FLOPs count;
    no recomputation)."""
    attn = 12 * model["n_layers"] * model["n_heads"] * model["head_dim"] * seq
    return 6.0 * matmul_params(model) + attn


def packed_rows(model: dict) -> int:
    """Rows R of the (R, 128) commit buffer: each tensor padded to whole
    rows, the total padded to a whole row tile."""
    rows = sum(math.ceil(n / LANES) for n in leaf_sizes(model))
    return -(-rows // ROW_ALIGN) * ROW_ALIGN


def commit_bytes(rows: int, k: int) -> int:
    """HBM bytes the packed commit kernels must move for a commit of k
    arrivals. One arrival: the statistics pass reads delta and momentum
    (2R) and the fused sweep reads params, momentum and delta and writes
    params and momentum (3R + 2W). A fused flush of k: the Gram pass
    reads momentum and k deltas (k + 1), the multi sweep reads params,
    momentum and k deltas and writes params and momentum (k + 4)."""
    row = LANES * F32
    if k == 1:
        return 7 * rows * row
    return (2 * k + 5) * rows * row

