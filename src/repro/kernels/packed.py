"""Pallas kernels over the packed (R, 128) arrival buffer.

These collapse the per-leaf arrival pipeline (2 ``pallas_call`` per block
for the correction + a second full tree sweep for the outer update —
O(#leaves) launches and ~2x the minimal HBM traffic) into exactly TWO
launches per pseudo-gradient, independent of how many tensors the model
has:

  packed_row_stats     one sweep reading (delta, momentum) -> per-row
                       partial (dot, uu, vv); a tiny O(R) segment-sum over
                       the static row->block map turns that into per-block
                       statistics (R = d/128, so the segment reduction is
                       negligible next to the O(d) sweep).
  packed_correct_outer one fused sweep reading (p, m, delta) tiles plus a
                       per-row (cu, cv) scalar table, writing (p', m') —
                       Alg. 2 correction and the Eq. 17-19 Nesterov outer
                       update in a single pass: 3 reads + 2 writes of d
                       floats, the roofline minimum for this update.

Plus per-row-scale int8 quantization (``packed_rowabs`` / ``packed_quant``
/ ``packed_dequant``) so compression round-trips are also one launch per
sweep instead of per-leaf.

Branch-scalar computation (``branch_scalars``) is vectorised over all B
blocks at once — O(B) elementwise work on tiny arrays.

Padding contract: zero rows contribute zero to every statistic and map to
zero under the fused update (p=m=delta=0 stays 0), so the packed buffer's
padding never needs re-zeroing between arrivals.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.configs.base import HeLoCoConfig
from repro.kernels.tiling import LANES, row_tile


def _grid(r: int, interpret: bool, rows: int | None = None):
    rows = row_tile(r, interpret, rows)
    return rows, (r // rows,)


# ---------------------------------------------------------------------------
# Sweep 1: per-row correction statistics (segment-reduction friendly)
# ---------------------------------------------------------------------------

def _rowstats_kernel(u_ref, v_ref, out_ref):
    u = u_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.stack(
        [jnp.sum(u * v, axis=1), jnp.sum(u * u, axis=1),
         jnp.sum(v * v, axis=1)], axis=1)


def packed_row_stats(u2d: jnp.ndarray, v2d: jnp.ndarray,
                     interpret: bool = True,
                     rows: int | None = None) -> jnp.ndarray:
    """u2d, v2d: (R, 128). One read of each; returns (R, 3) row partials."""
    r = u2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    return pl.pallas_call(
        _rowstats_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((rows, 3), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 3), jnp.float32),
        interpret=interpret,
    )(u2d, v2d)


def packed_stats(u2d: jnp.ndarray, v2d: jnp.ndarray, row_block: jnp.ndarray,
                 n_blocks: int, interpret: bool = True,
                 ranges=None) -> jnp.ndarray:
    """Per-block (dot, uu, vv): one O(d) sweep + an O(R) segment reduction.

    ranges: optional static ((start_row, end_row), ...) per block (see
    ``BlockLayout.block_row_ranges``) — blocks are contiguous row spans,
    so the reduction lowers to static slices, ~6x cheaper than the
    scatter-based segment sum used when only ``row_block`` is available.
    """
    parts = packed_row_stats(u2d, v2d, interpret=interpret)
    if ranges is not None:
        return jnp.stack([parts[s:e].sum(axis=0) for s, e in ranges])
    return jax.ops.segment_sum(parts, jnp.asarray(row_block),
                               num_segments=n_blocks,
                               indices_are_sorted=True)


# ---------------------------------------------------------------------------
# Branch scalars, vectorised over blocks (paper Alg. 2 / Eqs. 7-16)
# ---------------------------------------------------------------------------

def branch_scalars(stats: jnp.ndarray, h: HeLoCoConfig):
    """(B, 3) per-block (dot, uu, vv) -> per-block (cu, cv), each (B,).

    The corrected pseudo-gradient of every block is ``cu*u + cv*v``; cu/cv
    encode the keep / anti-aligned-damp / weak-aligned-rotate branch
    exactly as in ``ops.heloco_correct_block``, but for all blocks at once.
    """
    dot, uu, vv = stats[:, 0], stats[:, 1], stats[:, 2]
    nu = jnp.sqrt(uu)
    nv = jnp.sqrt(vv)
    c = dot / jnp.maximum(nu * nv, h.eps * h.eps)
    conf = nu / (nu + h.kappa * nv + h.eps)

    beta = jnp.minimum(h.k_s * (-c) * conf, h.beta_max)
    anti_cv = -beta * c * nu / jnp.maximum(nv, h.eps)

    lam = jnp.minimum(h.k_d * (1.0 - c) * conf, 1.0)
    nt = jnp.sqrt((1 - lam) ** 2 + lam ** 2 + 2 * lam * (1 - lam) * c)
    wscale = nu / jnp.maximum(nt, h.eps)
    weak_cu = wscale * (1 - lam) / jnp.maximum(nu, h.eps)
    weak_cv = wscale * lam / jnp.maximum(nv, h.eps)

    keep = c >= h.c_ok
    antib = c < 0.0
    degen = (nu < h.eps) | (nv < h.eps)
    cu = jnp.where(degen | keep, 1.0, jnp.where(antib, 1.0, weak_cu))
    cv = jnp.where(degen | keep, 0.0, jnp.where(antib, anti_cv, weak_cv))
    return cu, cv


# ---------------------------------------------------------------------------
# Sweep 2: fused correct + Nesterov outer update
# ---------------------------------------------------------------------------

# Per-row telemetry moments (see repro.telemetry.stats): each fused sweep
# already reads (delta, momentum) tiles, so update-quality diagnostics are
# emitted as ONE extra per-row output of the SAME launch — [d.m, d.d, m.m,
# |g_unweighted - d|^2] partials, reduced outside the kernel. The p'/m'
# arithmetic of the stats variants is op-for-op identical to the plain
# kernels, so enabling telemetry cannot move a single output bit.
N_MOMENTS = 4


def _row_moments(d, m, corr):
    return jnp.stack([jnp.sum(d * m, axis=1), jnp.sum(d * d, axis=1),
                      jnp.sum(m * m, axis=1),
                      jnp.sum((corr - d) * (corr - d), axis=1)], axis=1)


def _correct_outer_kernel(p_ref, m_ref, d_ref, cu_ref, cv_ref, hp_ref,
                          p_out, m_out):
    eta = hp_ref[0, 0]
    mu = hp_ref[0, 1]
    rho = hp_ref[0, 2]
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    g = (cu_ref[...] * d + cv_ref[...] * m) * rho    # corrected, weighted
    m_new = mu * m + (1.0 - mu) * g
    p_out[...] = (p - eta * (g + mu * m_new)).astype(p_out.dtype)
    m_out[...] = m_new


def _correct_outer_stats_kernel(p_ref, m_ref, d_ref, cu_ref, cv_ref, hp_ref,
                                p_out, m_out, s_out):
    eta = hp_ref[0, 0]
    mu = hp_ref[0, 1]
    rho = hp_ref[0, 2]
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    g = (cu_ref[...] * d + cv_ref[...] * m) * rho    # corrected, weighted
    m_new = mu * m + (1.0 - mu) * g
    p_out[...] = (p - eta * (g + mu * m_new)).astype(p_out.dtype)
    m_out[...] = m_new
    s_out[...] = _row_moments(d, m, cu_ref[...] * d + cv_ref[...] * m)


def packed_correct_outer(p2d: jnp.ndarray, m2d: jnp.ndarray,
                         d2d: jnp.ndarray, cu_rows: jnp.ndarray,
                         cv_rows: jnp.ndarray, eta: float, mu: float, rho,
                         interpret: bool = True, rows: int | None = None,
                         with_stats: bool = False):
    """One fused sweep: g = cu*delta + cv*m per row, then Eqs. 17-19.

    p2d/m2d/d2d: (R, 128); cu_rows/cv_rows: (R, 1) per-row branch scalars
    (each block's scalar replicated over its rows). Returns (p', m'), plus
    an (R, 4) per-row telemetry-moment output when ``with_stats`` — same
    single launch, identical p'/m' arithmetic.
    """
    r = p2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    hp = jnp.stack([jnp.asarray(eta, jnp.float32),
                    jnp.asarray(mu, jnp.float32),
                    jnp.asarray(rho, jnp.float32)]).reshape(1, 3)
    out_specs = [
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
        jax.ShapeDtypeStruct(m2d.shape, jnp.float32),
    ]
    if with_stats:
        out_specs.append(pl.BlockSpec((rows, N_MOMENTS), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((r, N_MOMENTS), jnp.float32))
    return pl.pallas_call(
        _correct_outer_stats_kernel if with_stats else _correct_outer_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(p2d, m2d, d2d, cu_rows, cv_rows, hp)


# ---------------------------------------------------------------------------
# Sweep 2 variants for the generalized method layer (repro.core.methods).
# Same contract as packed_correct_outer — ONE fused launch, one read of
# each input tile, one write of each output tile — but with the extra
# per-method terms: a quadratic delay-compensation coefficient (cq) and/or
# a gradient-accumulator buffer with schedule scalars (am, bm, ab, cg, cm).
# Methods pick their variant through their packed hook; this module never
# branches on method names.
# ---------------------------------------------------------------------------

def _correct_outer_quad_kernel(p_ref, m_ref, d_ref, cu_ref, cv_ref, cq_ref,
                               hp_ref, p_out, m_out):
    eta = hp_ref[0, 0]
    mu = hp_ref[0, 1]
    rho = hp_ref[0, 2]
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    g = (cu_ref[...] * d + cv_ref[...] * m
         + cq_ref[...] * d * d * m) * rho       # Taylor-compensated, weighted
    m_new = mu * m + (1.0 - mu) * g
    p_out[...] = (p - eta * (g + mu * m_new)).astype(p_out.dtype)
    m_out[...] = m_new


def _correct_outer_quad_stats_kernel(p_ref, m_ref, d_ref, cu_ref, cv_ref,
                                     cq_ref, hp_ref, p_out, m_out, s_out):
    eta = hp_ref[0, 0]
    mu = hp_ref[0, 1]
    rho = hp_ref[0, 2]
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    g = (cu_ref[...] * d + cv_ref[...] * m
         + cq_ref[...] * d * d * m) * rho       # Taylor-compensated, weighted
    m_new = mu * m + (1.0 - mu) * g
    p_out[...] = (p - eta * (g + mu * m_new)).astype(p_out.dtype)
    m_out[...] = m_new
    s_out[...] = _row_moments(
        d, m, cu_ref[...] * d + cv_ref[...] * m + cq_ref[...] * d * d * m)


def packed_correct_outer_quad(p2d: jnp.ndarray, m2d: jnp.ndarray,
                              d2d: jnp.ndarray, cu_rows: jnp.ndarray,
                              cv_rows: jnp.ndarray, cq_rows: jnp.ndarray,
                              eta: float, mu: float, rho,
                              interpret: bool = True,
                              rows: int | None = None,
                              with_stats: bool = False):
    """One fused sweep with a quadratic compensation term per row:
    g = cu*delta + cv*m + cq*delta^2*m, then Eqs. 17-19. Returns (p', m')
    (+ (R, 4) telemetry moments when ``with_stats``, same launch)."""
    r = p2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    hp = jnp.stack([jnp.asarray(eta, jnp.float32),
                    jnp.asarray(mu, jnp.float32),
                    jnp.asarray(rho, jnp.float32)]).reshape(1, 3)
    out_specs = [
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
        jax.ShapeDtypeStruct(m2d.shape, jnp.float32),
    ]
    if with_stats:
        out_specs.append(pl.BlockSpec((rows, N_MOMENTS), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((r, N_MOMENTS), jnp.float32))
    return pl.pallas_call(
        (_correct_outer_quad_stats_kernel if with_stats
         else _correct_outer_quad_kernel),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(p2d, m2d, d2d, cu_rows, cv_rows, cq_rows, hp)


def _correct_outer_acc_kernel(p_ref, m_ref, b_ref, d_ref, cu_ref, cv_ref,
                              hp_ref, p_out, m_out, b_out):
    eta = hp_ref[0, 0]
    rho = hp_ref[0, 1]
    am = hp_ref[0, 2]
    bm = hp_ref[0, 3]
    ab = hp_ref[0, 4]
    cg = hp_ref[0, 5]
    cm = hp_ref[0, 6]
    ca = hp_ref[0, 7]
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    g = (cu_ref[...] * d + cv_ref[...] * m) * rho
    acc = b + g
    m_new = am * m + bm * acc
    p_out[...] = (p - eta * (cg * g + ca * acc + cm * m_new)
                  ).astype(p_out.dtype)
    m_out[...] = m_new
    b_out[...] = ab * acc


def _correct_outer_acc_stats_kernel(p_ref, m_ref, b_ref, d_ref, cu_ref,
                                    cv_ref, hp_ref, p_out, m_out, b_out,
                                    s_out):
    eta = hp_ref[0, 0]
    rho = hp_ref[0, 1]
    am = hp_ref[0, 2]
    bm = hp_ref[0, 3]
    ab = hp_ref[0, 4]
    cg = hp_ref[0, 5]
    cm = hp_ref[0, 6]
    ca = hp_ref[0, 7]
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    g = (cu_ref[...] * d + cv_ref[...] * m) * rho
    acc = b + g
    m_new = am * m + bm * acc
    p_out[...] = (p - eta * (cg * g + ca * acc + cm * m_new)
                  ).astype(p_out.dtype)
    m_out[...] = m_new
    b_out[...] = ab * acc
    s_out[...] = _row_moments(d, m, cu_ref[...] * d + cv_ref[...] * m)


def packed_correct_outer_acc(p2d: jnp.ndarray, m2d: jnp.ndarray,
                             b2d: jnp.ndarray, d2d: jnp.ndarray,
                             cu_rows: jnp.ndarray, cv_rows: jnp.ndarray,
                             eta: float, rho, am, bm, ab, cg, cm, ca=0.0,
                             interpret: bool = True,
                             rows: int | None = None,
                             with_stats: bool = False):
    """One fused sweep of the generalized schedule with a gradient
    accumulator (delayed-Nesterov / FedBuff family):

      g = (cu*delta + cv*m)*rho;  acc = b + g
      m' = am*m + bm*acc;  b' = ab*acc
      p' = p - eta*(cg*g + ca*acc + cm*m')

    Schedule scalars may be traced (boundary arrivals toggle them).
    Returns (p', m', b') (+ (R, 4) telemetry moments when ``with_stats``,
    same launch)."""
    r = p2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    hp = jnp.stack([jnp.asarray(eta, jnp.float32),
                    jnp.asarray(rho, jnp.float32),
                    jnp.asarray(am, jnp.float32),
                    jnp.asarray(bm, jnp.float32),
                    jnp.asarray(ab, jnp.float32),
                    jnp.asarray(cg, jnp.float32),
                    jnp.asarray(cm, jnp.float32),
                    jnp.asarray(ca, jnp.float32)]).reshape(1, 8)
    out_specs = [
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
        jax.ShapeDtypeStruct(m2d.shape, jnp.float32),
        jax.ShapeDtypeStruct(b2d.shape, jnp.float32),
    ]
    if with_stats:
        out_specs.append(pl.BlockSpec((rows, N_MOMENTS), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((r, N_MOMENTS), jnp.float32))
    return pl.pallas_call(
        (_correct_outer_acc_stats_kernel if with_stats
         else _correct_outer_acc_kernel),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(p2d, m2d, b2d, d2d, cu_rows, cv_rows, hp)


# ---------------------------------------------------------------------------
# Batched multi-arrival sweeps (K coalesced deltas, ONE launch).
#
# The server's commit buffer coalesces up to K pending arrivals and flushes
# them through these kernels: a (K, R, 128) delta stack plus per-delta
# (K, R, 1) coefficient rows and a (K, n_hp) scalar table. The kernel
# unrolls the K applications in registers — p and m round-trip through
# fp32 registers instead of fp32 HBM between applications, which is the
# identity, so the result is op-order-IDENTICAL to K sequential launches
# of the single-arrival kernels whenever the per-delta coefficients match
# what the sequential path would have computed. HBM traffic drops from
# K*(3R+2W) to (K+2)R+2W of d floats; launches from K (or 2K) to 1.
#
# Telemetry moments ride the same sweep as a (K, R, 4) extra output,
# each slice computed against the momentum as of THAT application — the
# same values K sequential with_stats launches would emit.
# ---------------------------------------------------------------------------


def _multi_hp(k: int, *cols) -> jnp.ndarray:
    """Per-delta scalar table: each col is a scalar or (K,) -> (K, #cols)."""
    cols = [jnp.broadcast_to(jnp.asarray(c, jnp.float32), (k,)) for c in cols]
    return jnp.stack(cols, axis=1)


def _multi_correct_outer_kernel(k: int, with_stats: bool):
    def kern(p_ref, m_ref, d_ref, cu_ref, cv_ref, hp_ref, p_out, m_out,
             *s_out):
        p = p_ref[...].astype(jnp.float32)
        m = m_ref[...].astype(jnp.float32)
        for j in range(k):
            eta = hp_ref[j, 0]
            mu = hp_ref[j, 1]
            rho = hp_ref[j, 2]
            d = d_ref[j].astype(jnp.float32)
            corr = cu_ref[j] * d + cv_ref[j] * m
            if with_stats:
                s_out[0][j] = _row_moments(d, m, corr)
            g = corr * rho
            m_new = mu * m + (1.0 - mu) * g
            p = p - eta * (g + mu * m_new)
            m = m_new
        p_out[...] = p.astype(p_out.dtype)
        m_out[...] = m
    return kern


def packed_multi_correct_outer(p2d: jnp.ndarray, m2d: jnp.ndarray,
                               d3d: jnp.ndarray, cu_rows: jnp.ndarray,
                               cv_rows: jnp.ndarray, eta, mu, rho,
                               interpret: bool = True,
                               rows: int | None = None,
                               with_stats: bool = False):
    """K fused correct+outer applications in ONE launch.

    d3d: (K, R, 128) delta stack; cu_rows/cv_rows: (K, R, 1) per-delta
    coefficient rows; eta/mu/rho: scalar or (K,) per-delta. Returns
    (p', m') after all K applications (+ (K, R, 4) per-row telemetry
    moments when ``with_stats``, one slice per delta, same launch).
    """
    k, r = d3d.shape[0], p2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    hp = _multi_hp(k, eta, mu, rho)
    out_specs = [
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
        jax.ShapeDtypeStruct(m2d.shape, jnp.float32),
    ]
    if with_stats:
        out_specs.append(pl.BlockSpec((k, rows, N_MOMENTS),
                                      lambda i: (0, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((k, r, N_MOMENTS),
                                              jnp.float32))
    return pl.pallas_call(
        _multi_correct_outer_kernel(k, with_stats),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((k, rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((k, rows, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((k, rows, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((k, 3), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(p2d, m2d, d3d, cu_rows, cv_rows, hp)


def _multi_correct_outer_quad_kernel(k: int, with_stats: bool):
    def kern(p_ref, m_ref, d_ref, cu_ref, cv_ref, cq_ref, hp_ref, p_out,
             m_out, *s_out):
        p = p_ref[...].astype(jnp.float32)
        m = m_ref[...].astype(jnp.float32)
        for j in range(k):
            eta = hp_ref[j, 0]
            mu = hp_ref[j, 1]
            rho = hp_ref[j, 2]
            d = d_ref[j].astype(jnp.float32)
            corr = cu_ref[j] * d + cv_ref[j] * m + cq_ref[j] * d * d * m
            if with_stats:
                s_out[0][j] = _row_moments(d, m, corr)
            g = corr * rho
            m_new = mu * m + (1.0 - mu) * g
            p = p - eta * (g + mu * m_new)
            m = m_new
        p_out[...] = p.astype(p_out.dtype)
        m_out[...] = m
    return kern


def packed_multi_correct_outer_quad(p2d: jnp.ndarray, m2d: jnp.ndarray,
                                    d3d: jnp.ndarray, cu_rows: jnp.ndarray,
                                    cv_rows: jnp.ndarray,
                                    cq_rows: jnp.ndarray, eta, mu, rho,
                                    interpret: bool = True,
                                    rows: int | None = None,
                                    with_stats: bool = False):
    """K quadratic-compensated applications in one launch (multi variant
    of :func:`packed_correct_outer_quad`); cq_rows: (K, R, 1)."""
    k, r = d3d.shape[0], p2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    hp = _multi_hp(k, eta, mu, rho)
    out_specs = [
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
        jax.ShapeDtypeStruct(m2d.shape, jnp.float32),
    ]
    if with_stats:
        out_specs.append(pl.BlockSpec((k, rows, N_MOMENTS),
                                      lambda i: (0, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((k, r, N_MOMENTS),
                                              jnp.float32))
    return pl.pallas_call(
        _multi_correct_outer_quad_kernel(k, with_stats),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((k, rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((k, rows, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((k, rows, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((k, rows, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((k, 3), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(p2d, m2d, d3d, cu_rows, cv_rows, cq_rows, hp)


def _multi_correct_outer_acc_kernel(k: int, with_stats: bool):
    def kern(p_ref, m_ref, b_ref, d_ref, cu_ref, cv_ref, hp_ref, p_out,
             m_out, b_out, *s_out):
        p = p_ref[...].astype(jnp.float32)
        m = m_ref[...].astype(jnp.float32)
        b = b_ref[...].astype(jnp.float32)
        for j in range(k):
            eta = hp_ref[j, 0]
            rho = hp_ref[j, 1]
            am = hp_ref[j, 2]
            bm = hp_ref[j, 3]
            ab = hp_ref[j, 4]
            cg = hp_ref[j, 5]
            cm = hp_ref[j, 6]
            ca = hp_ref[j, 7]
            d = d_ref[j].astype(jnp.float32)
            corr = cu_ref[j] * d + cv_ref[j] * m
            if with_stats:
                s_out[0][j] = _row_moments(d, m, corr)
            g = corr * rho
            acc = b + g
            m_new = am * m + bm * acc
            p = p - eta * (cg * g + ca * acc + cm * m_new)
            m = m_new
            b = ab * acc
        p_out[...] = p.astype(p_out.dtype)
        m_out[...] = m
        b_out[...] = b
    return kern


def packed_multi_correct_outer_acc(p2d: jnp.ndarray, m2d: jnp.ndarray,
                                   b2d: jnp.ndarray, d3d: jnp.ndarray,
                                   cu_rows: jnp.ndarray,
                                   cv_rows: jnp.ndarray,
                                   eta, rho, am, bm, ab, cg, cm, ca=0.0,
                                   interpret: bool = True,
                                   rows: int | None = None,
                                   with_stats: bool = False):
    """K accumulator-schedule applications in one launch (multi variant of
    :func:`packed_correct_outer_acc`); every schedule scalar may be a
    per-delta (K,) vector — boundary arrivals inside the batch toggle
    their own slot. Returns (p', m', b')."""
    k, r = d3d.shape[0], p2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    hp = _multi_hp(k, eta, rho, am, bm, ab, cg, cm, ca)
    out_specs = [
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
        jax.ShapeDtypeStruct(m2d.shape, jnp.float32),
        jax.ShapeDtypeStruct(b2d.shape, jnp.float32),
    ]
    if with_stats:
        out_specs.append(pl.BlockSpec((k, rows, N_MOMENTS),
                                      lambda i: (0, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((k, r, N_MOMENTS),
                                              jnp.float32))
    return pl.pallas_call(
        _multi_correct_outer_acc_kernel(k, with_stats),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((k, rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((k, rows, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((k, rows, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((k, 8), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(p2d, m2d, b2d, d3d, cu_rows, cv_rows, hp)


def _multi_gram_kernel(k: int):
    t = k + 1
    def kern(m_ref, d_ref, out_ref):
        vecs = [m_ref[...].astype(jnp.float32)]
        vecs += [d_ref[j].astype(jnp.float32) for j in range(k)]
        cols = []
        for a in range(t):
            for b in range(a, t):
                cols.append(jnp.sum(vecs[a] * vecs[b], axis=1))
        out_ref[...] = jnp.stack(cols, axis=1)
    return kern


def packed_multi_gram(m2d: jnp.ndarray, d3d: jnp.ndarray, ranges,
                      interpret: bool = True,
                      rows: int | None = None) -> jnp.ndarray:
    """Per-block Gram matrix of the batch basis [m0, d_1..d_K].

    One sweep reading (m, d-stack) emits per-row pairwise products of the
    K+1 basis vectors; the static ``ranges`` slices (see
    ``BlockLayout.block_row_ranges``) reduce them to per-block sums.
    Returns (B, K+1, K+1) symmetric Gram matrices. Every inner product a
    sequential flush would measure — between any delta and the EVOLVING
    momentum — is a linear functional of this Gram (the momentum after j
    applications stays inside span[m0, d_1..d_j]), so one launch replaces
    the K stats sweeps of the sequential path.
    """
    k, r = d3d.shape[0], m2d.shape[0]
    t = k + 1
    p_cols = t * (t + 1) // 2
    rows, grid = _grid(r, interpret, rows)
    parts = pl.pallas_call(
        _multi_gram_kernel(k),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((k, rows, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((rows, p_cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, p_cols), jnp.float32),
        interpret=interpret,
    )(m2d, d3d)
    blocks = jnp.stack([parts[s:e].sum(axis=0) for s, e in ranges])
    idx = np.zeros((t, t), np.int32)
    c = 0
    for a in range(t):
        for b in range(a, t):
            idx[a, b] = idx[b, a] = c
            c += 1
    return blocks[:, idx]


# ---------------------------------------------------------------------------
# Per-row-scale int8 quantization (packed compression path)
# ---------------------------------------------------------------------------

def _rowabs_kernel(x_ref, out_ref):
    out_ref[...] = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)),
                           axis=1, keepdims=True)


def packed_rowabs(x2d: jnp.ndarray, interpret: bool = True,
                  rows: int | None = None) -> jnp.ndarray:
    """(R, 128) -> (R, 1) per-row absmax in one sweep."""
    r = x2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    return pl.pallas_call(
        _rowabs_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.float32),
        interpret=interpret,
    )(x2d)


def _quant_kernel(x_ref, s_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.clip(jnp.round(x / s_ref[...]), -127, 127
                            ).astype(jnp.int8)


def packed_quant(x2d: jnp.ndarray, scale_rows: jnp.ndarray,
                 interpret: bool = True,
                 rows: int | None = None) -> jnp.ndarray:
    """Quantize with a per-row scale table; scale_rows: (R, 1), > 0."""
    r = x2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.int8),
        interpret=interpret,
    )(x2d, scale_rows)


def _dequant_kernel(q_ref, s_ref, out_ref):
    out_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]
                    ).astype(out_ref.dtype)


def packed_dequant(q2d: jnp.ndarray, scale_rows: jnp.ndarray,
                   out_dtype=jnp.float32, interpret: bool = True,
                   rows: int | None = None):
    r = q2d.shape[0]
    rows, grid = _grid(r, interpret, rows)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(q2d.shape, out_dtype),
        interpret=interpret,
    )(q2d, scale_rows)


# ---------------------------------------------------------------------------
# Launch accounting
# ---------------------------------------------------------------------------

def count_launches(fn, *args) -> int:
    """``pallas_call`` equations in the traced program of ``fn(*args)``,
    nested jaxprs included: the kernel dispatches one execution performs
    (robust to jit caching across same-shape blocks)."""
    def walk(jx) -> int:
        n = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(sub, ClosedJaxpr):
                        n += walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        n += walk(sub)
        return n
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)
