"""Precision of the reference's matrix products, shared by every
architecture module (``chipbench/arch/<kind>.py``).

``matmul`` names it: "bf16" follows the configuration (bfloat16 operands,
float32 accumulation); "fp8" rounds every operand to float8 e4m3 with a
per-tensor scale first, the lower precision that serves as the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0                       # largest finite float8 e4m3fn
MATMULS = ("bf16", "fp8")


def f8(x):
    """Round to float8 e4m3 with one scale for the whole tensor; the
    gradient passes through unrounded."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / F8_MAX
    q = (xf / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return (xf + jax.lax.stop_gradient(q - xf)).astype(jnp.bfloat16)


def operand(matmul: str):
    """What happens to a bfloat16 operand before a product: nothing at
    "bf16", ``f8`` at "fp8"."""
    if matmul not in MATMULS:
        raise ValueError(f"unknown matmul precision {matmul!r}; "
                         f"known: {MATMULS}")
    return f8 if matmul == "fp8" else (lambda x: x)


def product(matmul: str):
    """mm(spec, a, b): an einsum of bfloat16 operands (each through
    ``operand``) with float32 accumulation and a bfloat16 result."""
    q8 = operand(matmul)
    bf = jnp.bfloat16

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a.astype(bf)), q8(b.astype(bf)),
                          preferred_element_type=jnp.float32).astype(bf)

    return mm
