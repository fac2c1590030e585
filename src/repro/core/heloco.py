"""HeLoCo: momentum-guided look-ahead initialization + per-tensor-block
directional correction of stale pseudo-gradients (paper Sections 3, Alg. 1-2).

Everything here is pure JAX and jittable. A "block" is a leaf tensor of the
parameter pytree — exactly the paper's granularity ("each block is an
individual model tensor"). For scanned layer stacks (leaves carrying a
leading layer axis) the correction is vmapped over that axis so granularity
matches the unstacked model; pass ``stacked_axes`` describing how many
leading axes of each leaf are layer axes.

Two arrival implementations share the same math (verified equivalent in
tests/test_packed.py):

  apply_arrival         per-leaf pytree path — the correctness reference
  apply_arrival_packed  fast path over the packed (R, 128) buffer from
                        ``repro.core.packing``: one stats sweep + one fused
                        correct+outer sweep, O(1) kernel launches per
                        arrival (see docs/packed_layout.md)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import HeLoCoConfig

PyTree = Any


class OuterState(NamedTuple):
    """Synchronizer state: outer params + Nesterov momentum buffer.

    ``aux`` is per-method auxiliary state (``None`` for the standard
    Nesterov schedule; a gradient-accumulator pytree for buffered methods
    such as delayed-Nesterov — see ``repro.core.methods``)."""
    params: PyTree
    momentum: PyTree
    step: jnp.ndarray          # outer step t (int32)
    aux: Optional[PyTree] = None


def init_outer_state(params: PyTree, with_aux: bool = False) -> OuterState:
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    aux = (jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32),
                        params) if with_aux else None)
    return OuterState(params=params, momentum=zeros,
                      step=jnp.zeros((), jnp.int32), aux=aux)


# ---------------------------------------------------------------------------
# Eq. 5: momentum-guided look-ahead worker initialization
# ---------------------------------------------------------------------------

def lookahead_init(state: OuterState, outer_lr: float, mu: float) -> PyTree:
    """theta_bar_r = theta_r - eta_r * mu * m_r  (HeLoCo + MLA worker init)."""
    return jax.tree.map(
        lambda p, m: (p.astype(jnp.float32) - outer_lr * mu * m).astype(p.dtype),
        state.params, state.momentum)


# ---------------------------------------------------------------------------
# Eqs. 7-16 / Alg. 2: per-block directional correction
# ---------------------------------------------------------------------------

def correct_block(delta: jnp.ndarray, mom: jnp.ndarray,
                  h: HeLoCoConfig) -> jnp.ndarray:
    """Correct ONE tensor block against its momentum block.

    Flattens the block, computes the cosine c_b and applies:
      c_b >= c_ok           : keep
      c_b <  0              : damp the anti-momentum component   (Eq. 10-11)
      0 <= c_b < c_ok       : norm-preserving rotation to v_hat  (Eq. 12-14)
      degenerate norms      : pass through
    """
    u = delta.astype(jnp.float32).reshape(-1)
    v = mom.astype(jnp.float32).reshape(-1)
    nu = jnp.linalg.norm(u)
    nv = jnp.linalg.norm(v)
    safe_nu = jnp.maximum(nu, h.eps)
    safe_nv = jnp.maximum(nv, h.eps)
    u_hat = u / safe_nu
    v_hat = v / safe_nv
    c = jnp.dot(u_hat, v_hat, precision="highest")                # Eq. 8
    conf = nu / (nu + h.kappa * nv + h.eps)                       # Eq. 15

    # anti-aligned branch (Eq. 10-11)
    beta = jnp.minimum(h.k_s * (-c) * conf, h.beta_max)
    anti = u - beta * c * nu * v_hat

    # weakly-aligned branch (Eq. 12-14)
    lam = jnp.minimum(h.k_d * (1.0 - c) * conf, 1.0)
    u_tilde = (1.0 - lam) * u_hat + lam * v_hat
    weak = nu * u_tilde / jnp.maximum(jnp.linalg.norm(u_tilde), h.eps)

    corrected = jnp.where(c >= h.c_ok, u, jnp.where(c < 0.0, anti, weak))
    degenerate = (nu < h.eps) | (nv < h.eps)
    out = jnp.where(degenerate, u, corrected)
    return out.reshape(delta.shape).astype(delta.dtype)


def block_correct(delta: PyTree, momentum: PyTree, h: HeLoCoConfig,
                  stacked_axes: Optional[PyTree] = None,
                  use_kernel: bool = False) -> PyTree:
    """Alg. 2 over the whole pseudo-gradient pytree.

    stacked_axes: optional pytree of ints (same structure) giving the number
    of leading layer axes per leaf (scanned stacks); the correction is
    vmapped over those axes so each layer's tensor is its own block.
    use_kernel: route each block through the fused Pallas kernel path.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        base = functools.partial(kops.heloco_correct_block, h=h)
    else:
        base = functools.partial(correct_block, h=h)

    if stacked_axes is None:
        return jax.tree.map(base, delta, momentum)

    def apply_one(d, m, n_axes):
        fn = base
        for _ in range(int(n_axes)):
            fn = jax.vmap(fn)
        return fn(d, m)

    return jax.tree.map(apply_one, delta, momentum, stacked_axes)


# ---------------------------------------------------------------------------
# Eqs. 17-19: outer update (shared by Nesterov / MLA / HeLoCo)
# ---------------------------------------------------------------------------

def outer_update(state: OuterState, g: PyTree, outer_lr: float,
                 mu: float, rho: jnp.ndarray | float = 1.0) -> OuterState:
    """m_{t+1} = mu m_t + (1-mu) rho G;  theta_{t+1} = theta_t - eta (G' + mu m_{t+1})."""
    def m_upd(m, gi):
        return mu * m + (1.0 - mu) * rho * gi.astype(jnp.float32)

    def p_upd(p, m_new, gi):
        gf = rho * gi.astype(jnp.float32)
        return (p.astype(jnp.float32) - outer_lr * (gf + mu * m_new)).astype(p.dtype)

    momentum = jax.tree.map(m_upd, state.momentum, g)
    params = jax.tree.map(p_upd, state.params, momentum, g)
    return OuterState(params=params, momentum=momentum, step=state.step + 1,
                      aux=state.aux)


# ---------------------------------------------------------------------------
# Method dispatch: what happens when a pseudo-gradient arrives.
# All per-method behaviour lives in the ``repro.core.methods`` registry;
# the drivers below are method-agnostic.
# ---------------------------------------------------------------------------

def mla_correct(delta: PyTree, momentum: PyTree, outer_lr: float,
                mu: float, tau: jnp.ndarray,
                tau_clip: float = 10.0) -> PyTree:
    """Momentum Look-Ahead (Ajanthan et al. 2025): uniform extrapolation of
    the whole pseudo-gradient along the negative momentum direction,
    proportional to staleness: Delta' = Delta + eta * mu * tau_norm * m,
    with tau_norm = min(tau, tau_clip)/tau_clip (the paper's clip lives on
    the method definition in ``repro.core.methods``).

    (The original MLA applies a single uniform momentum-based shift to the
    entire update; per-block geometry is exactly what it lacks.)
    """
    scale = (outer_lr * mu
             * jnp.minimum(tau.astype(jnp.float32), tau_clip) / tau_clip)
    return jax.tree.map(
        lambda d, m: (d.astype(jnp.float32) + scale * m).astype(d.dtype),
        delta, momentum)


def momentum_decay_update(state: OuterState, outer_lr: float, mu: float,
                          method="heloco",
                          rho: jnp.ndarray | float = 1.0,
                          tau: jnp.ndarray | float = 0.0,
                          phase=None) -> OuterState:
    """Outer step for a DROPPED stale arrival (App. A.6). Equivalent to
    ``apply_arrival`` with a zero pseudo-gradient (for every registered
    method, incl. MLA's momentum extrapolation of the zero delta) but
    skips materialising the zero pytree and the O(d) correction entirely.
    """
    from repro.core import methods as _methods
    m = _methods.resolve(method)
    ctx = _methods.ArrivalCtx(outer_lr=outer_lr, mu=mu, rho=rho,
                              tau=jnp.asarray(tau, jnp.float32), phase=phase)
    if m.custom_update:
        return _methods.scheduled_decay_update(m, ctx, state)
    c_m, c_p = _methods.decay_coeffs(m, ctx)
    momentum = jax.tree.map(lambda mm: c_m * mm, state.momentum)
    params = jax.tree.map(
        lambda p, mm: (p.astype(jnp.float32) - outer_lr * c_p * mm
                       ).astype(p.dtype),
        state.params, state.momentum)
    return OuterState(params=params, momentum=momentum, step=state.step + 1,
                      aux=state.aux)


def apply_arrival(state: OuterState, delta: PyTree, *, method,
                  outer_lr: float, mu: float, h: HeLoCoConfig,
                  rho: jnp.ndarray | float = 1.0,
                  tau: jnp.ndarray | float = 0.0,
                  stacked_axes: Optional[PyTree] = None,
                  use_kernel: bool = False, phase=None) -> OuterState:
    """Process one arriving pseudo-gradient through the chosen method.

    method: any registered ``repro.core.methods`` name/alias or an
    ``OuterMethod`` instance (for sync methods, `delta` is already the
    worker-averaged pseudo-gradient). ``phase`` is the outer-step index at
    arrival — only buffered schedules (delayed-Nesterov) read it.
    """
    from repro.core import methods as _methods
    m = _methods.resolve(method)
    tau = jnp.asarray(tau)
    ctx = _methods.ArrivalCtx(outer_lr=outer_lr, mu=mu, h=h, rho=rho,
                              tau=tau, phase=phase,
                              stacked_axes=stacked_axes,
                              use_kernel=use_kernel)
    g = m.correct(m, ctx, delta, state.momentum)
    if m.custom_update:
        return _methods.scheduled_outer_update(m, ctx, state, g)
    return outer_update(state, g, outer_lr, mu, rho=rho)


def apply_arrivals(state: OuterState, deltas, *, method, outer_lr: float,
                   mu: float, h: HeLoCoConfig, rhos=None, taus=None,
                   phases=None, stacked_axes: Optional[PyTree] = None,
                   use_kernel: bool = False) -> OuterState:
    """Per-leaf REFERENCE of a batched flush: K sequential
    ``apply_arrival`` steps with per-delta rho/tau/phase. This is the
    semantics ``apply_arrivals_packed`` must reproduce (fp32-close; the
    property tests in tests/test_scale.py pin it for every method)."""
    k = len(deltas)
    rhos = [1.0] * k if rhos is None else list(rhos)
    taus = [0.0] * k if taus is None else list(taus)
    phases = [None] * k if phases is None else list(phases)
    for delta, rho, tau, phase in zip(deltas, rhos, taus, phases):
        state = apply_arrival(state, delta, method=method, outer_lr=outer_lr,
                              mu=mu, h=h, rho=rho, tau=tau, phase=phase,
                              stacked_axes=stacked_axes,
                              use_kernel=use_kernel)
    return state


# ---------------------------------------------------------------------------
# Packed fast path: same math, one flat buffer, O(1) kernel launches
# ---------------------------------------------------------------------------

def apply_arrival_packed(pbuf: jnp.ndarray, mbuf: jnp.ndarray,
                         delta: PyTree, layout, *, method,
                         outer_lr: float, mu: float, h: HeLoCoConfig,
                         rho: jnp.ndarray | float = 1.0,
                         tau: jnp.ndarray | float = 0.0,
                         abuf: jnp.ndarray | None = None, phase=None,
                         interpret: bool | None = None,
                         with_stats: bool = False):
    """Process one arrival on the packed (R, 128) outer state.

    pbuf/mbuf: packed fp32 params / momentum (see ``repro.core.packing``);
    abuf: the method's packed auxiliary buffer (buffered methods only).
    delta: the arriving pseudo-gradient pytree (packed here — one fused
    XLA gather/concat, no kernel launches). Returns (pbuf', mbuf') or
    (pbuf', mbuf', abuf') for buffered methods.

    with_stats: additionally return the (R, 4) per-row telemetry moments
    ``[d.m, d.d, m.m, |g_unweighted - d|^2]`` as the LAST element — they
    are an extra output of the same fused sweep, so the launch count and
    the update bytes are unchanged (see ``repro.telemetry``).

    Numerically equivalent to ``apply_arrival`` on fp32 pytrees: every
    registered method reduces to per-block scalars (cu, cv, cq) with
    g = cu*delta + cv*m + cq*delta^2*m (see ``repro.core.methods``), so
    the whole arrival is at most ONE statistics sweep (methods that need
    segment stats, e.g. HeLoCo) plus ONE fused correct+outer sweep —
    <= 2 pallas_calls regardless of #leaves, vs 2 per leaf + a second
    full tree sweep on the per-leaf path.
    """
    from repro.core import methods as _methods
    from repro.core import packing
    from repro.kernels import packed as pk
    from repro.kernels.ops import _auto_interpret

    m = _methods.resolve(method)
    interpret = _auto_interpret(interpret)
    tau = jnp.asarray(tau)
    row_block = jnp.asarray(layout.row_block)
    dbuf = packing.pack(layout, delta)
    ctx = _methods.ArrivalCtx(outer_lr=outer_lr, mu=mu, h=h, rho=rho,
                              tau=tau, phase=phase, layout=layout,
                              interpret=interpret)
    cu, cv, cq = m.packed_coeffs(m, ctx, dbuf, mbuf)
    cu_rows = cu[row_block][:, None]
    cv_rows = cv[row_block][:, None]
    if m.custom_update:          # same dispatch as the per-leaf driver
        if cq is not None:
            raise NotImplementedError(
                f"method {m.name!r}: a quadratic (cq) term combined with "
                "a custom schedule is not supported on the packed path")
        am, bm, ab, cg, cm, ca = _methods.schedule_coeffs(m, ctx)
        if abuf is None:
            abuf = packing.zeros(layout)
        out = pk.packed_correct_outer_acc(
            pbuf, mbuf, abuf, dbuf, cu_rows, cv_rows, outer_lr, rho,
            am, bm, ab, cg, cm, ca, interpret=interpret,
            with_stats=with_stats)
        if m.uses_buffer:
            return out
        return (out[0], out[1], out[3]) if with_stats else out[:2]
    if cq is not None:
        cq_rows = cq[row_block][:, None]
        return pk.packed_correct_outer_quad(
            pbuf, mbuf, dbuf, cu_rows, cv_rows, cq_rows, outer_lr, mu,
            rho, interpret=interpret, with_stats=with_stats)
    return pk.packed_correct_outer(pbuf, mbuf, dbuf, cu_rows, cv_rows,
                                   outer_lr, mu, rho, interpret=interpret,
                                   with_stats=with_stats)


def apply_arrivals_packed(pbuf: jnp.ndarray, mbuf: jnp.ndarray,
                          deltas, layout, *, method,
                          outer_lr: float, mu: float, h: HeLoCoConfig,
                          rhos, taus, abuf: jnp.ndarray | None = None,
                          phases=None, interpret: bool | None = None,
                          with_stats: bool = False):
    """Process K coalesced arrivals on the packed outer state in at most
    TWO Pallas launches total (one optional multi-Gram statistics sweep +
    one K-unrolled fused sweep), vs up to 2K for the sequential path.

    deltas: sequence of K pseudo-gradient pytrees in commit order; rhos /
    taus: per-delta scalars (sequence of K); phases: per-delta outer-step
    indices (buffered schedules only). Semantics are those of K sequential
    ``apply_arrival_packed`` calls with the momentum evolving between
    them — byte-identical modulo fp32 instruction scheduling (the K
    applications chain through registers instead of HBM). K = 1 callers
    should use ``apply_arrival_packed`` directly, which is bitwise
    byte-identical to the pre-batching path.

    with_stats: additionally return (K, R, 4) per-row telemetry moments,
    slice j computed against the momentum as of application j — same
    launch, same count.
    """
    from repro.core import methods as _methods
    from repro.core import packing
    from repro.kernels import packed as pk
    from repro.kernels.ops import _auto_interpret

    m = _methods.resolve(method)
    interpret = _auto_interpret(interpret)
    k = len(deltas)
    row_block = jnp.asarray(layout.row_block)
    dstack = jnp.stack([packing.pack(layout, d) for d in deltas])
    phases = [None] * k if phases is None else list(phases)
    ctxs = [_methods.ArrivalCtx(outer_lr=outer_lr, mu=mu, h=h, rho=rho,
                                tau=jnp.asarray(tau, jnp.float32),
                                phase=phase, layout=layout,
                                interpret=interpret)
            for rho, tau, phase in zip(rhos, taus, phases)]
    cu, cv, cq = _methods.multi_packed_coeffs(m, ctxs, dstack, mbuf)
    cu_rows = cu[:, row_block][:, :, None]
    cv_rows = cv[:, row_block][:, :, None]
    rho_vec = jnp.stack([jnp.asarray(r, jnp.float32) for r in rhos])
    if m.custom_update:
        if cq is not None:
            raise NotImplementedError(
                f"method {m.name!r}: a quadratic (cq) term combined with "
                "a custom schedule is not supported on the packed path")
        am, bm, ab, cg, cm, ca = _methods.multi_schedule_coeffs(m, ctxs)
        if abuf is None:
            abuf = packing.zeros(layout)
        out = pk.packed_multi_correct_outer_acc(
            pbuf, mbuf, abuf, dstack, cu_rows, cv_rows, outer_lr, rho_vec,
            am, bm, ab, cg, cm, ca, interpret=interpret,
            with_stats=with_stats)
        if m.uses_buffer:
            return out
        return (out[0], out[1], out[3]) if with_stats else out[:2]
    if cq is not None:
        cq_rows = cq[:, row_block][:, :, None]
        return pk.packed_multi_correct_outer_quad(
            pbuf, mbuf, dstack, cu_rows, cv_rows, cq_rows, outer_lr, mu,
            rho_vec, interpret=interpret, with_stats=with_stats)
    return pk.packed_multi_correct_outer(
        pbuf, mbuf, dstack, cu_rows, cv_rows, outer_lr, mu, rho_vec,
        interpret=interpret, with_stats=with_stats)


def momentum_decay_packed(pbuf: jnp.ndarray, mbuf: jnp.ndarray,
                          outer_lr: float, mu: float,
                          method="heloco",
                          rho: jnp.ndarray | float = 1.0,
                          tau: jnp.ndarray | float = 0.0,
                          abuf: jnp.ndarray | None = None, phase=None):
    """Dropped-arrival step on packed state (see ``methods.decay_coeffs``).
    Pure elementwise buffer math (XLA fuses it into one pass)."""
    from repro.core import methods as _methods
    m = _methods.resolve(method)
    ctx = _methods.ArrivalCtx(outer_lr=outer_lr, mu=mu, rho=rho,
                              tau=jnp.asarray(tau, jnp.float32), phase=phase)
    if m.custom_update:
        return _methods.scheduled_decay_packed(m, ctx, pbuf, mbuf, abuf)
    c_m, c_p = _methods.decay_coeffs(m, ctx)
    return pbuf - outer_lr * c_p * mbuf, c_m * mbuf
