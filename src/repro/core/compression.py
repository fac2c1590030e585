"""Pseudo-gradient compression with error feedback (beyond-paper,
DiLoCoX-style). Applied on the worker before shipping Delta to the
synchronizer; the error-feedback buffer keeps compression unbiased over
time. Cuts the pod-axis collective bytes by 4x (int8) or ~10x (top-k).

Two int8 paths:
  * per-leaf (``compress``/``decompress``): one scale per tensor, one
    quantize/dequantize pair per leaf — the original reference path.
  * packed (``packed_int8_roundtrip`` and the ``layout=`` argument of
    ``roundtrip_with_error_feedback``): the pytree is flattened through a
    ``repro.core.packing.BlockLayout`` and quantized per BLOCK (same
    granularity, finer for stacked-layer leaves) with O(1) kernel launches
    per round-trip instead of O(#leaves); the error-feedback buffer also
    lives packed. The whole worker-side step (pack, ``+ ef``, the three
    flat sweeps absmax / quantize / dequantize over one (R, 128) buffer,
    the per-block scales and the residual) is ONE compiled program,
    traced once per layout (and once more for the first round's
    ``ef=None``), so a round dispatches it and lowers nothing.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.kernels import packed as pk
from repro.kernels.ops import _auto_interpret

PyTree = Any


class Compressed(NamedTuple):
    payload: PyTree           # int8 values / (values, indices)
    scale: PyTree             # per-tensor scales (fp32)
    kind: str


def _int8_one(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _int8_decode(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def _topk_one(x: jnp.ndarray, ratio: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    flat = x.astype(jnp.float32).reshape(-1)
    k = max(1, int(flat.size * ratio))
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx.astype(jnp.int32)


def compress(delta: PyTree, kind: str, topk_ratio: float = 0.1) -> Compressed:
    if kind == "int8":
        qs = jax.tree.map(_int8_one, delta)
        payload = jax.tree.map(lambda t: t[0], qs,
                               is_leaf=lambda t: isinstance(t, tuple))
        scale = jax.tree.map(lambda t: t[1], qs,
                             is_leaf=lambda t: isinstance(t, tuple))
        return Compressed(payload, scale, "int8")
    if kind == "topk":
        qs = jax.tree.map(lambda x: _topk_one(x, topk_ratio), delta)
        return Compressed(
            jax.tree.map(lambda t: (t[0], t[1]), qs,
                         is_leaf=lambda t: isinstance(t, tuple)),
            jax.tree.map(lambda x: jnp.asarray(x.shape, jnp.int32), delta),
            "topk")
    raise ValueError(kind)


def decompress(c: Compressed, like: PyTree) -> PyTree:
    if c.kind == "int8":
        return jax.tree.map(_int8_decode, c.payload, c.scale)
    if c.kind == "topk":
        def dec(pair, ref):
            vals, idx = pair
            flat = jnp.zeros(ref.size, jnp.float32).at[idx].set(vals)
            return flat.reshape(ref.shape)
        return jax.tree.map(dec, c.payload, like,
                            is_leaf=lambda t: isinstance(t, tuple))
    raise ValueError(c.kind)


def compressed_bytes(c: Compressed) -> int:
    if c.kind == "int8":
        n = sum(x.size for x in jax.tree.leaves(c.payload))
        return n + 4 * len(jax.tree.leaves(c.scale))
    vals = jax.tree.leaves(c.payload)
    return sum(x.size * x.dtype.itemsize for x in vals)


def _packed_int8_program(delta: PyTree, ef: Optional[jnp.ndarray], layout,
                         interpret: bool, rows: int | None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    dbuf = packing.pack(layout, delta)
    target = dbuf if ef is None else dbuf + ef
    rowabs = pk.packed_rowabs(target, interpret=interpret, rows=rows)[:, 0]
    # blocks are contiguous row spans: static slices beat a segment max
    blockabs = jnp.stack([rowabs[s:e].max()
                          for s, e in layout.block_row_ranges])
    # a true division, as the eager op computed it: XLA turns a division
    # by a constant into a multiply by its (rounded) reciprocal
    scale = (jnp.maximum(blockabs, 1e-12)
             / jax.lax.optimization_barrier(jnp.float32(127.0)))
    scale_rows = scale[layout.row_block][:, None]
    q = pk.packed_quant(target, scale_rows, interpret=interpret, rows=rows)
    decoded = pk.packed_dequant(q, scale_rows, interpret=interpret,
                                rows=rows)
    return decoded, target - decoded


_STATIC = ("layout", "interpret", "rows")
_compiled_program = jax.jit(_packed_int8_program, static_argnames=_STATIC)
# The interpreter inlines the kernels into the program, where XLA:CPU's
# fusion would contract the dequantize multiply into the residual's
# subtract (an FMA). Unfused, every op rounds as the eager ops do.
_interpreted_program = jax.jit(
    _packed_int8_program, static_argnames=_STATIC,
    compiler_options={"xla_disable_hlo_passes": "fusion"})


def packed_int8_roundtrip(delta: PyTree, ef: Optional[jnp.ndarray], layout,
                          interpret: bool | None = None,
                          rows: int | None = None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Per-block int8 fake-quantization of ``pack(layout, delta) + ef``.

    One absmax sweep + an O(R) per-block max gives per-block scales; one
    quantize and one dequantize sweep complete the round-trip — 3 kernel
    launches total regardless of #blocks, all inside one jitted program
    keyed on the (hashable) layout and on whether ``ef`` is None.
    Returns (decoded_buf, new_ef, wire_bytes): new_ef = target - decoded
    in fp32, and wire_bytes counts only real elements (int8) + one fp32
    scale per block, matching the per-leaf accounting. ``ef`` is not
    donated. rows: kernel row-tile override (tests: multi-step grids).
    """
    interpret = _auto_interpret(interpret)
    program = _interpreted_program if interpret else _compiled_program
    decoded, new_ef = program(delta, ef, layout, interpret, rows)
    nbytes = int(layout.total_elems) + 4 * layout.n_blocks
    return decoded, new_ef, nbytes


def roundtrip_with_error_feedback(delta: PyTree, ef: Optional[PyTree],
                                  kind: str, topk_ratio: float = 0.1,
                                  layout=None
                                  ) -> Tuple[PyTree, PyTree, int]:
    """Worker-side: compress (delta + ef), return (decoded, new_ef, bytes).

    decoded is what the synchronizer receives after decompression; new_ef
    accumulates what compression lost (error feedback).

    layout: optional ``repro.core.packing.BlockLayout`` for ``delta``.
    With kind="int8" it routes the round-trip through the packed buffer
    (O(1) kernel launches); ``ef`` is then a packed (R, 128) buffer, not a
    pytree (``None`` still means "no error accumulated yet"), and the
    decoded value is returned as a ``packing.Packed`` buffer so the packed
    synchronizer consumes it without an unpack -> re-pack detour.
    """
    if kind == "int8" and layout is not None:
        decoded_buf, new_ef, nbytes = packed_int8_roundtrip(delta, ef, layout)
        return packing.Packed(decoded_buf), new_ef, nbytes
    if kind == "none":
        zeros = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), delta)
        nbytes = sum(x.size * 4 for x in jax.tree.leaves(delta))
        return delta, zeros, nbytes
    if ef is None:
        ef = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), delta)
    target = jax.tree.map(lambda d, e: d.astype(jnp.float32) + e, delta, ef)
    comp = compress(target, kind, topk_ratio)
    decoded = decompress(comp, target)
    new_ef = jax.tree.map(lambda t, d: t - d, target, decoded)
    return decoded, new_ef, compressed_bytes(comp)
