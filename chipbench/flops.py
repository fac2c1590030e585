"""Bytes the trainer's work needs, from the configuration's shapes alone
(no code of the program): the shapes of the architecture module's
weights (``chipbench/arch/<kind>.py``), whose ``train_flops_per_token``
counts the operations."""
from __future__ import annotations

import math
from typing import List

import jax

LANES = 128                  # floats per row of the packed commit buffer
ROW_ALIGN = 8                # rows: the buffer is padded to a whole tile
F32 = 4


def leaf_sizes(arch, model: dict) -> List[int]:
    """Element count of every parameter tensor: the leaves of the
    architecture's weights, by shape alone."""
    shapes = jax.eval_shape(lambda: arch.init_params(model, 0))
    return [math.prod(x.shape) for x in jax.tree.leaves(shapes)]


def n_params(arch, model: dict) -> int:
    return sum(leaf_sizes(arch, model))


def packed_rows(arch, model: dict) -> int:
    """Rows R of the (R, 128) commit buffer: each tensor padded to whole
    rows, the total padded to a whole row tile."""
    rows = sum(math.ceil(n / LANES) for n in leaf_sizes(arch, model))
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
