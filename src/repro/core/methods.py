"""The pluggable outer-method layer: ONE registry from kernels to scenarios.

An :class:`OuterMethod` is the single source of truth for everything a
method means across the stack:

  * per-leaf reference correction (``correct`` hook — the math the paper
    states, used by ``apply_arrival`` and the dist outer exchange);
  * packed-path hooks (``packed_coeffs``: which segment stats the fused
    kernel needs + the per-block coefficient triple ``(cu, cv, cq)`` with
    ``g = cu*delta + cv*m + cq*delta^2*m`` — so ``kernels/packed.py``
    never branches on method strings);
  * dropped-arrival decay behaviour (``decay_scale``: the scalar ``s``
    with ``G = s*m`` when the pseudo-gradient is suppressed, generalizing
    the old ``_decay_coeffs``);
  * the outer-update *schedule* (``outer_coeffs``: ``(am, bm, ab, cg,
    cm)`` — ``None`` means the standard Nesterov update of Eqs. 17-19;
    methods with ``buffer_period > 0`` additionally keep a gradient
    accumulator, e.g. delayed-Nesterov);
  * look-ahead-init participation (replacing the hard-coded
    ``method in ("heloco", "mla")`` gate in the synchronizer);
  * Table-3 outer-optimizer defaults and the benchmark-dialect aliases
    ("async-heloco", ...) that the scenario layer and benchmarks resolve
    through :func:`canonical` — no duplicated alias tables.

Adding a method is ~50 lines: define the hooks, ``register(OuterMethod(
...))``, and it automatically rides the packed fast path, the wall-clock
runtime, the scenario registry, and the golden-trace CI gate (see
docs/methods.md for a worked example).

This module is the ONLY place allowed to encode per-method behaviour;
``grep -rn 'method ==' src/ benchmarks/`` must stay empty outside it.

Generalized update (one fused packed sweep, see ``kernels/packed.py``):

    G    = rho * (cu*Delta + cv*m + cq*Delta^2*m)     # corrected, weighted
    acc  = b + G                                       # gradient buffer
    m'   = am*m + bm*acc
    b'   = ab*acc
    p'   = p - eta*(cg*G + ca*acc + cm*m')

``outer_coeffs`` may return 5 coefficients ``(am, bm, ab, cg, cm)`` —
``ca`` defaults to 0 — or all 6; ``ca`` lets buffered-aggregation methods
(FedBuff) step the parameters with the accumulator average at a boundary.
The standard Nesterov schedule is ``(am, bm, ab, cg, cm) = (mu, 1-mu, 0,
1, mu)`` with ``b = 0``, which collapses to Eqs. 17-19 exactly.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import HeLoCoConfig

PyTree = Any


# ---------------------------------------------------------------------------
# Arrival context: everything a hook may read
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ArrivalCtx:
    """Per-arrival inputs threaded to every hook. ``rho``/``tau``/``phase``
    may be traced scalars (the synchronizer jits over them)."""
    outer_lr: float
    mu: float
    h: Optional[HeLoCoConfig] = None
    rho: Any = 1.0
    tau: Any = 0.0                   # staleness (fp32 scalar)
    phase: Any = None                # outer-step index at arrival (int32);
    # None means step 0 — only buffered schedules read it
    stacked_axes: Optional[PyTree] = None
    use_kernel: bool = False
    layout: Any = None               # packing.BlockLayout (packed path only)
    interpret: Optional[bool] = None


def _phase(ctx: ArrivalCtx):
    return jnp.asarray(0 if ctx.phase is None else ctx.phase, jnp.int32)


def _tau_norm(ctx: ArrivalCtx, clip: float):
    """min(tau, clip)/clip — the shared staleness normalization (the MLA
    paper constant lives on the method definition, not inline)."""
    return jnp.minimum(jnp.asarray(ctx.tau).astype(jnp.float32), clip) / clip


# ---------------------------------------------------------------------------
# The method definition object
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OuterMethod:
    """Complete definition of one outer method (see module docstring)."""
    name: str
    description: str
    # -- Table-3 outer-optimizer defaults (paper Appendix A.5) --------------
    outer_lr: float
    momentum: float = 0.9
    weight_factor: str = "base"      # "base" sqrt(k)/k | "average" 1/k | "one"
    lookahead_init: bool = False     # Eq. 5 look-ahead participation AND its
    # Table-3 default (methods that can use it default it on)
    # -- identity -----------------------------------------------------------
    aliases: Tuple[str, ...] = ()    # benchmark-dialect names ("async-heloco")
    sync: bool = False               # barrier method: engines run sync rounds
    outer_lr_cap: Optional[float] = None   # launcher clamp (async Nesterov)
    # -- method constants ---------------------------------------------------
    tau_clip: float = 0.0            # staleness normalization clip (0 = n/a)
    dc_lambda: float = 0.0           # delay-compensation strength (dcasgd)
    stale_alpha: float = 0.0         # polynomial staleness exponent
    buffer_period: int = 0           # >0: gradient accumulator, momentum
    # refresh every N arrivals (delayed-Nesterov / FedBuff)
    batchable: bool = True           # False: the server's commit buffer must
    # flush before/after every arrival of this method (ordering constraint)
    # -- hooks --------------------------------------------------------------
    correct: Callable = None         # (m, ctx, delta, momentum) -> g pytree
    packed_coeffs: Callable = None   # (m, ctx, dbuf, mbuf) -> (cu, cv, cq)
    packed_multi_coeffs: Callable = None  # (m, ctxs, dstack, mbuf) ->
    # per-delta ((K,B) cu, (K,B) cv, (K,B) cq | None) for a flush of K
    # coalesced arrivals; None -> the generic per-delta loop (exact for
    # hooks that never read the momentum buffer — every momentum-DEPENDENT
    # hook must supply its own, as heloco does via the Gram recursion)
    decay_scale: Callable = None     # (m, ctx) -> scalar s (G = s*m, delta=0)
    outer_coeffs: Callable = None    # (m, ctx) -> (am, bm, ab, cg, cm[, ca]);
    # None -> the standard Nesterov schedule (byte-identical legacy path)

    def __post_init__(self):
        assert self.weight_factor in ("base", "average", "one"), \
            self.weight_factor
        assert self.correct is not None and self.packed_coeffs is not None, \
            f"method {self.name!r} must define correct + packed_coeffs hooks"
        if self.decay_scale is None:
            object.__setattr__(self, "decay_scale", _zero_decay)

    # ------------------------------------------------------------ structure
    @property
    def uses_buffer(self) -> bool:
        return self.buffer_period > 0

    @property
    def custom_update(self) -> bool:
        """True when the outer update deviates from the standard Nesterov
        schedule (extra state and/or non-default coefficients)."""
        return self.uses_buffer or self.outer_coeffs is not None

    def defaults(self) -> Dict[str, Any]:
        """The Table-3 preset row (the old METHOD_TABLE entry shape)."""
        return dict(outer_lr=self.outer_lr, momentum=self.momentum,
                    weight_factor=self.weight_factor,
                    lookahead_init=self.lookahead_init)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, OuterMethod] = {}
_ALIASES: Dict[str, str] = {}


def register(m: OuterMethod) -> OuterMethod:
    if m.name in _REGISTRY or m.name in _ALIASES:
        raise ValueError(f"duplicate outer method name {m.name!r}")
    for a in m.aliases:
        if a in _ALIASES or a in _REGISTRY:
            raise ValueError(f"duplicate outer method alias {a!r}")
    _REGISTRY[m.name] = m
    for a in m.aliases:
        _ALIASES[a] = m.name
    return m


def get(name: str) -> OuterMethod:
    """Look up a method by canonical name or benchmark-dialect alias."""
    key = _ALIASES.get(name, name)
    try:
        return _REGISTRY[key]
    except KeyError:
        raise KeyError(f"unknown outer method {name!r}; registered: "
                       f"{', '.join(sorted(_REGISTRY))} (aliases: "
                       f"{', '.join(sorted(_ALIASES))})") from None


def resolve(method) -> OuterMethod:
    """Accept an OuterMethod instance or any registered name/alias."""
    if isinstance(method, OuterMethod):
        return method
    return get(method)


def canonical(name: str) -> str:
    return get(name).name


def names() -> List[str]:
    return list(_REGISTRY)


def all_methods() -> List[OuterMethod]:
    return list(_REGISTRY.values())


def cli_names() -> List[str]:
    """Canonical names + aliases (the launcher's --method choices)."""
    return sorted(_REGISTRY) + sorted(_ALIASES)


def method_table() -> Dict[str, Dict[str, Any]]:
    """Table-3 defaults keyed by canonical name — the registry view that
    replaced the hand-maintained METHOD_TABLE dict."""
    return {m.name: m.defaults() for m in _REGISTRY.values()}


def alias_table() -> Dict[str, str]:
    """Benchmark-dialect alias -> canonical name (the registry view that
    replaced METHOD_PRESETS / the benchmarks.common duplicate)."""
    return dict(_ALIASES)


# ---------------------------------------------------------------------------
# Generic update drivers (used by core.heloco for non-standard schedules)
# ---------------------------------------------------------------------------

def standard_coeffs(mu):
    """(am, bm, ab, cg, cm) of the plain Nesterov schedule (Eqs. 17-19)."""
    return mu, 1.0 - mu, 0.0, 1.0, mu


def schedule_coeffs(m: OuterMethod, ctx: ArrivalCtx):
    """The method's 6-tuple ``(am, bm, ab, cg, cm, ca)`` — pads legacy
    5-tuple ``outer_coeffs`` hooks with ``ca = 0``."""
    c = m.outer_coeffs(m, ctx) if m.outer_coeffs else standard_coeffs(ctx.mu)
    return (*c, 0.0) if len(c) == 5 else c


def decay_coeffs(m: OuterMethod, ctx: ArrivalCtx):
    """Scalar coefficients of the dropped-arrival outer step for methods on
    the STANDARD schedule. With the pseudo-gradient suppressed the
    corrected gradient collapses to G = s*m (``decay_scale``), so
      m' = c_m m;  theta' = theta - eta c_p m
    and no zero pytree / O(d) correction sweep is ever needed."""
    g = ctx.rho * m.decay_scale(m, ctx)
    c_m = ctx.mu + (1.0 - ctx.mu) * g
    c_p = g + ctx.mu * c_m
    return c_m, c_p


def scheduled_outer_update(m: OuterMethod, ctx: ArrivalCtx, state, g):
    """Per-leaf generalized outer step (see module docstring) for methods
    whose schedule deviates from plain Nesterov (``custom_update``)."""
    from repro.core.heloco import OuterState
    eta, rho = ctx.outer_lr, ctx.rho
    am, bm, ab, cg, cm, ca = schedule_coeffs(m, ctx)
    aux = state.aux
    if aux is None:
        aux = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                           state.momentum)
    acc = jax.tree.map(lambda b, gi: b + rho * gi.astype(jnp.float32),
                       aux, g)
    momentum = jax.tree.map(lambda mm, a: am * mm + bm * a,
                            state.momentum, acc)
    params = jax.tree.map(
        lambda p, mnew, a, gi: (p.astype(jnp.float32)
                                - eta * (cg * rho * gi.astype(jnp.float32)
                                         + ca * a + cm * mnew)
                                ).astype(p.dtype),
        state.params, momentum, acc, g)
    new_aux = jax.tree.map(lambda a: ab * a, acc)
    return OuterState(params=params, momentum=momentum,
                      step=state.step + 1,
                      aux=new_aux if m.uses_buffer else None)


def scheduled_decay_update(m: OuterMethod, ctx: ArrivalCtx, state):
    """Dropped-arrival step for ``custom_update`` methods: the generalized
    update applied to the collapsed gradient g = s*m (``decay_scale``).
    Unlike the standard-schedule scalar fast path this materialises one
    pytree, but it shares the update math with ``scheduled_outer_update``
    exactly — the decay-collapse identity holds by construction."""
    s = m.decay_scale(m, ctx)
    g = jax.tree.map(lambda mm: s * mm, state.momentum)
    return scheduled_outer_update(m, ctx, state, g)


def multi_packed_coeffs(m: OuterMethod, ctxs, dstack, mbuf):
    """Per-delta coefficient rows for a flush of K coalesced arrivals.

    ctxs: one :class:`ArrivalCtx` per delta, in commit order; dstack:
    (K, R, 128). Returns ``(cu, cv, cq)`` with cu/cv (K, B) and cq either
    ``None`` or (K, B) — the coefficients each application j would have
    seen on the sequential path (i.e. against the momentum as of THAT
    application). The default evaluates ``packed_coeffs`` per delta
    against the flush-time momentum buffer, which is exact precisely when
    the hook never reads ``mbuf``; momentum-dependent hooks override
    (heloco's override reconstructs the evolving-momentum statistics from
    one Gram sweep, keeping the whole flush at <= 2 launches)."""
    if m.packed_multi_coeffs is not None:
        return m.packed_multi_coeffs(m, ctxs, dstack, mbuf)
    outs = [m.packed_coeffs(m, ctx, dstack[j], mbuf)
            for j, ctx in enumerate(ctxs)]
    cu = jnp.stack([o[0] for o in outs])
    cv = jnp.stack([o[1] for o in outs])
    if outs[0][2] is None:
        return cu, cv, None
    return cu, cv, jnp.stack([o[2] for o in outs])


def multi_schedule_coeffs(m: OuterMethod, ctxs):
    """Stack :func:`schedule_coeffs` over a flush: six (K,) vectors
    ``(am, bm, ab, cg, cm, ca)`` — each delta's boundary state toggles its
    own slot of the multi acc kernel's scalar table."""
    rows = [schedule_coeffs(m, ctx) for ctx in ctxs]
    return tuple(jnp.stack([jnp.asarray(r[i], jnp.float32) for r in rows])
                 for i in range(6))


def scheduled_decay_packed(m: OuterMethod, ctx: ArrivalCtx, pbuf, mbuf,
                           abuf=None):
    """Packed dropped-arrival step for ``custom_update`` methods. Pure
    elementwise buffer math (XLA fuses it into one pass)."""
    eta, rho = ctx.outer_lr, ctx.rho
    am, bm, ab, cg, cm, ca = schedule_coeffs(m, ctx)
    s = m.decay_scale(m, ctx)
    if abuf is None:
        abuf = jnp.zeros_like(mbuf)
    g = rho * s * mbuf
    acc = abuf + g
    m_new = am * mbuf + bm * acc
    p_new = pbuf - eta * (cg * g + ca * acc + cm * m_new)
    if m.uses_buffer:
        return p_new, m_new, ab * acc
    return p_new, m_new


# ---------------------------------------------------------------------------
# Hook implementations
# ---------------------------------------------------------------------------

def _zero_decay(m, ctx):
    """Zero delta collapses to G = 0 (heloco / nesterov / dcasgd / DN)."""
    return 0.0


def _identity_correct(m, ctx, delta, momentum):
    """Nesterov-family: the pseudo-gradient is applied as-is."""
    return delta


def _plain_packed_coeffs(m, ctx, dbuf, mbuf):
    n = ctx.layout.n_blocks
    return jnp.ones((n,), jnp.float32), jnp.zeros((n,), jnp.float32), None


# -- HeLoCo (paper Alg. 2) ---------------------------------------------------

def _heloco_correct(m, ctx, delta, momentum):
    from repro.core.heloco import block_correct
    return block_correct(delta, momentum, ctx.h,
                         stacked_axes=ctx.stacked_axes,
                         use_kernel=ctx.use_kernel)


def _heloco_packed_coeffs(m, ctx, dbuf, mbuf):
    from repro.kernels import packed as pk
    stats = pk.packed_stats(dbuf, mbuf, jnp.asarray(ctx.layout.row_block),
                            ctx.layout.n_blocks, interpret=ctx.interpret,
                            ranges=ctx.layout.block_row_ranges)
    cu, cv = pk.branch_scalars(stats, ctx.h)
    return cu, cv, None


def _heloco_multi_coeffs(m, ctxs, dstack, mbuf):
    """Evolving-momentum branch statistics for K coalesced deltas from ONE
    Gram sweep. The momentum after j applications stays inside
    span[m0, d_1..d_j], so tracking its basis coordinates ``alpha`` (B,
    K+1) per block turns every (dot, uu, vv) the sequential path would
    measure into an O(B K^2) contraction of the per-block Gram matrix —
    no further O(d) work. fp32-close (not bitwise) to the sequential
    statistics for K > 1; K = 1 flushes take the single-arrival path."""
    from repro.kernels import packed as pk
    layout = ctxs[0].layout
    k = dstack.shape[0]
    gram = pk.packed_multi_gram(mbuf, dstack, layout.block_row_ranges,
                                interpret=ctxs[0].interpret)   # (B, K+1, K+1)
    alpha = jnp.zeros((layout.n_blocks, k + 1), jnp.float32)
    alpha = alpha.at[:, 0].set(1.0)                 # m_cur = 1 * m0
    cus, cvs = [], []
    for j, ctx in enumerate(ctxs):
        e = j + 1                                   # basis slot of d_j
        dot = jnp.sum(alpha * gram[:, e, :], axis=1)
        uu = gram[:, e, e]
        # full fp32: at default precision the TPU contracts f32 in bf16
        vv = jnp.sum(alpha * jnp.einsum("btu,bu->bt", gram, alpha,
                                        precision="highest"), axis=1)
        cu, cv = pk.branch_scalars(jnp.stack([dot, uu, vv], axis=1), ctx.h)
        cus.append(cu)
        cvs.append(cv)
        # m' = mu*m + (1-mu)*rho*(cu*d_j + cv*m), in basis coordinates
        rho = jnp.asarray(ctx.rho, jnp.float32)
        alpha = alpha * (ctx.mu + (1.0 - ctx.mu) * rho * cv)[:, None]
        alpha = alpha.at[:, e].add((1.0 - ctx.mu) * rho * cu)
    return jnp.stack(cus), jnp.stack(cvs), None


# -- MLA (momentum look-ahead; Ajanthan et al. 2025) -------------------------

def _mla_correct(m, ctx, delta, momentum):
    from repro.core.heloco import mla_correct
    return mla_correct(delta, momentum, ctx.outer_lr, ctx.mu,
                       jnp.asarray(ctx.tau), tau_clip=m.tau_clip)


def _mla_packed_coeffs(m, ctx, dbuf, mbuf):
    scale = ctx.outer_lr * ctx.mu * _tau_norm(ctx, m.tau_clip)
    n = ctx.layout.n_blocks
    return (jnp.ones((n,), jnp.float32),
            jnp.broadcast_to(scale, (n,)), None)


def _mla_decay_scale(m, ctx):
    """MLA of a zero delta is the nonzero G = eta*mu*tau_norm * m."""
    return ctx.outer_lr * ctx.mu * _tau_norm(ctx, m.tau_clip)


# -- delayed-Nesterov (Liu et al. 2024, Asynchronous Local-SGD) --------------

def _dn_outer_coeffs(m, ctx):
    """Buffer incoming (weighted) pseudo-gradients; every N-th arrival the
    momentum refreshes from the buffer average and the buffer resets:

      non-boundary:  b' = b + G;   m' = m;             p' = p - eta(G + mu m')
      boundary:      b' = 0;       m' = mu m + (1-mu)(b+G)/N;  same p' form
    """
    n = m.buffer_period
    boundary = (((_phase(ctx) + 1) % n) == 0).astype(jnp.float32)
    am = 1.0 - boundary * (1.0 - ctx.mu)
    bm = boundary * ((1.0 - ctx.mu) / n)
    ab = 1.0 - boundary
    return am, bm, ab, 1.0, ctx.mu


# -- FedBuff (Nguyen et al. 2022): K-arrival buffered aggregation ------------

def _fedbuff_outer_coeffs(m, ctx):
    """Buffer incoming (weighted) pseudo-gradients; the server only steps
    at every K-th arrival, applying the buffer AVERAGE through the plain
    Nesterov update, then resets the buffer:

      non-boundary:  b' = b + G;  m' = m;  p' = p
      boundary:      gbar = (b+G)/K;  m' = mu m + (1-mu) gbar;  b' = 0
                     p' = p - eta*(gbar + mu m')

    Between boundaries nothing moves — workers keep training from the
    last aggregate, the FedBuff semantics.
    """
    k = m.buffer_period
    boundary = (((_phase(ctx) + 1) % k) == 0).astype(jnp.float32)
    am = 1.0 - boundary * (1.0 - ctx.mu)
    bm = boundary * ((1.0 - ctx.mu) / k)
    ab = 1.0 - boundary
    cg = 0.0
    cm = boundary * ctx.mu
    ca = boundary / k
    return am, bm, ab, cg, cm, ca


# -- polynomial staleness weighting (Xie et al. 2019 style) ------------------

def _poly_weight(m, ctx):
    tau = jnp.asarray(ctx.tau).astype(jnp.float32)
    return (1.0 + tau) ** (-m.stale_alpha)


def _poly_correct(m, ctx, delta, momentum):
    """Damp the whole pseudo-gradient polynomially in its staleness:
    Delta' = (1 + tau)^-alpha * Delta (tau=0 recovers plain Nesterov)."""
    w = _poly_weight(m, ctx)
    return jax.tree.map(
        lambda d: (w * d.astype(jnp.float32)).astype(d.dtype), delta)


def _poly_packed_coeffs(m, ctx, dbuf, mbuf):
    n = ctx.layout.n_blocks
    return (jnp.broadcast_to(_poly_weight(m, ctx), (n,)),
            jnp.zeros((n,), jnp.float32), None)


# -- DC-ASGD-style delay compensation (Zheng et al. 2017) --------------------

def _dcasgd_correct(m, ctx, delta, momentum):
    """Taylor-style compensation of a stale pseudo-gradient: the server
    drift since dispatch is approximated along the momentum direction,
    theta_t - theta_bak ~ -eta * tau_norm * m, giving

      g~ = Delta + lambda * g^2 * (theta_t - theta_bak)
         = Delta - lambda * eta * tau_norm * (Delta (.) Delta (.) m)
    """
    coef = -(m.dc_lambda * ctx.outer_lr) * _tau_norm(ctx, m.tau_clip)

    def comp(d, mm):
        df = d.astype(jnp.float32)
        return (df + coef * df * df * mm.astype(jnp.float32)).astype(d.dtype)

    return jax.tree.map(comp, delta, momentum)


def _dcasgd_packed_coeffs(m, ctx, dbuf, mbuf):
    n = ctx.layout.n_blocks
    coef = -(m.dc_lambda * ctx.outer_lr) * _tau_norm(ctx, m.tau_clip)
    return (jnp.ones((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
            jnp.broadcast_to(coef, (n,)))


# ---------------------------------------------------------------------------
# The registered methods (paper Table 3 + the async Local-SGD baselines)
# ---------------------------------------------------------------------------

register(OuterMethod(
    name="heloco",
    description="Per-tensor-block directional correction of stale "
                "pseudo-gradients + momentum-guided look-ahead (paper "
                "Alg. 1-2).",
    outer_lr=0.7, momentum=0.9, weight_factor="base", lookahead_init=True,
    aliases=("async-heloco",),
    correct=_heloco_correct, packed_coeffs=_heloco_packed_coeffs,
    packed_multi_coeffs=_heloco_multi_coeffs))

register(OuterMethod(
    name="mla",
    description="Momentum Look-Ahead: uniform staleness-proportional "
                "extrapolation along the momentum (Ajanthan et al. 2025).",
    outer_lr=0.7, momentum=0.9, weight_factor="base", lookahead_init=True,
    aliases=("async-mla",), tau_clip=10.0,
    correct=_mla_correct, packed_coeffs=_mla_packed_coeffs,
    decay_scale=_mla_decay_scale))

register(OuterMethod(
    name="nesterov",
    description="Plain asynchronous Nesterov outer optimizer (async "
                "DiLoCo baseline; needs the reduced Table-3 outer LR).",
    outer_lr=0.07, momentum=0.9, weight_factor="base", lookahead_init=False,
    aliases=("async-nesterov",), outer_lr_cap=0.07,
    correct=_identity_correct, packed_coeffs=_plain_packed_coeffs))

register(OuterMethod(
    name="sync_nesterov",
    description="Synchronous DiLoCo/Nesterov barrier baseline: the "
                "slowest worker gates every round.",
    outer_lr=0.7, momentum=0.9, weight_factor="average",
    lookahead_init=False, aliases=("sync-nesterov",), sync=True,
    correct=_identity_correct, packed_coeffs=_plain_packed_coeffs))

register(OuterMethod(
    name="delayed_nesterov",
    description="Delayed Nesterov (Liu et al. 2024): buffer incoming "
                "pseudo-gradients, momentum step every N arrivals.",
    outer_lr=0.7, momentum=0.9, weight_factor="base", lookahead_init=False,
    aliases=("async-delayed-nesterov", "dn"), buffer_period=4,
    correct=_identity_correct, packed_coeffs=_plain_packed_coeffs,
    outer_coeffs=_dn_outer_coeffs))

register(OuterMethod(
    name="fedbuff",
    description="FedBuff-style buffered asynchronous aggregation: the "
                "server averages every K incoming pseudo-gradients into "
                "one outer Nesterov step (Nguyen et al. 2022).",
    outer_lr=0.7, momentum=0.9, weight_factor="one", lookahead_init=False,
    aliases=("async-fedbuff",), buffer_period=4,
    correct=_identity_correct, packed_coeffs=_plain_packed_coeffs,
    outer_coeffs=_fedbuff_outer_coeffs))

register(OuterMethod(
    name="poly_stale",
    description="Polynomial staleness weighting: the pseudo-gradient is "
                "damped by (1+tau)^-alpha before the Nesterov outer step "
                "(staleness-aware async SGD baseline).",
    outer_lr=0.07, momentum=0.9, weight_factor="base", lookahead_init=False,
    aliases=("async-poly-stale",), outer_lr_cap=0.07, stale_alpha=0.5,
    correct=_poly_correct, packed_coeffs=_poly_packed_coeffs))

register(OuterMethod(
    name="dcasgd",
    description="DC-ASGD-style Taylor delay compensation of stale "
                "pseudo-gradients, scaled by staleness tau.",
    outer_lr=0.07, momentum=0.9, weight_factor="base", lookahead_init=False,
    aliases=("async-dcasgd",), outer_lr_cap=0.07, tau_clip=10.0,
    dc_lambda=1.0,
    correct=_dcasgd_correct, packed_coeffs=_dcasgd_packed_coeffs))
