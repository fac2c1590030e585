"""The pluggable outer-method layer (repro.core.methods): registry
surface, per-method packed <-> per-leaf equivalence (property-based, for
EVERY registered method), the decay-collapse identity the dropped-arrival
fast path assumes, the buffered delayed-Nesterov schedule, and the
no-string-branches contract."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import HeLoCoConfig, OuterOptConfig
from repro.core import methods as M
from repro.core import packing
from repro.core.heloco import (
    apply_arrival, apply_arrival_packed, init_outer_state,
    momentum_decay_packed, momentum_decay_update,
)
from repro.async_engine.server import Synchronizer

H = HeLoCoConfig()

CANONICAL = ("heloco", "mla", "nesterov", "sync_nesterov",
             "delayed_nesterov", "dcasgd", "fedbuff", "poly_stale")


# ---------------------------------------------------------------------------
# Registry surface
# ---------------------------------------------------------------------------

def test_registry_names_aliases_and_table():
    names = M.names()
    for n in CANONICAL:
        assert n in names, n
    # aliases resolve to the same definition object
    assert M.get("async-heloco") is M.get("heloco")
    assert M.get("sync-nesterov") is M.get("sync_nesterov")
    assert M.canonical("async-delayed-nesterov") == "delayed_nesterov"
    with pytest.raises(KeyError):
        M.get("nope")
    # the Table-3 view matches the definitions field-for-field
    table = M.method_table()
    assert table["nesterov"]["outer_lr"] == 0.07
    assert table["sync_nesterov"]["weight_factor"] == "average"
    for m in M.all_methods():
        assert table[m.name] == m.defaults()
    # every alias maps onto a registered canonical name
    for alias, raw in M.alias_table().items():
        assert raw in table and alias in M.cli_names()


def test_register_rejects_duplicates():
    dup = M.OuterMethod(
        name="heloco", description="dup", outer_lr=0.1,
        correct=lambda m, c, d, mo: d,
        packed_coeffs=lambda m, c, db, mb: (None, None, None))
    with pytest.raises(ValueError):
        M.register(dup)


def test_structural_flags():
    assert M.get("sync_nesterov").sync
    assert not M.get("heloco").sync
    assert M.get("delayed_nesterov").uses_buffer
    assert M.get("delayed_nesterov").custom_update
    assert not M.get("dcasgd").uses_buffer
    assert not M.get("dcasgd").custom_update       # quad term, std schedule
    assert M.get("nesterov").outer_lr_cap == 0.07
    # MLA's magic staleness clip lives in exactly one place
    assert M.get("mla").tau_clip == 10.0


def test_lookahead_participation_replaces_string_gate():
    """Only methods with lookahead_init=True hand out the Eq. 5 model,
    even when the config flag is forced on (the old hard-coded
    ``method in ("heloco", "mla")`` gate, now data)."""
    params = {"w": jnp.ones((4, 4))}
    for name in ("heloco", "mla"):
        sv = Synchronizer(params, OuterOptConfig(method=name), 2)
        got = sv.worker_init()["w"]
        np.testing.assert_array_equal(np.asarray(got), 1.0)  # zero momentum
        assert sv.method.lookahead_init
    for name in ("nesterov", "delayed_nesterov", "dcasgd"):
        sv = Synchronizer(params, OuterOptConfig(method=name,
                                                 lookahead_init=True), 2)
        assert not sv.method.lookahead_init
        assert sv.worker_init() is sv.state.params


def test_no_method_string_branches_outside_registry():
    """The acceptance contract: no ``if method == ...`` dispatch anywhere
    outside core/methods.py."""
    src_root = pathlib.Path(M.__file__).resolve().parents[1]   # src/repro
    bench_root = src_root.parents[1] / "benchmarks"
    offenders = []
    for root in (src_root, bench_root):
        for p in root.rglob("*.py"):
            if p.name == "methods.py":
                continue
            if "method ==" in p.read_text():
                offenders.append(str(p))
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# Property suite: every registered method, random shapes / stacked axes
# ---------------------------------------------------------------------------

def _rand_tree(seed: int):
    """Random multi-leaf pytree incl. a stacked layer axis and an odd-size
    vector (padding boundary coverage)."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, 4))
    shapes = {
        "stack": (k, int(rng.integers(1, 5)), int(rng.integers(1, 7))),
        "mat": (int(rng.integers(1, 9)), int(rng.integers(1, 9))),
        "vec": (int(rng.integers(1, 150)),),
    }
    stacked = {"stack": 1, "mat": 0, "vec": 0}
    key = jax.random.PRNGKey(seed)

    def draw(i, shp):
        return jax.random.normal(jax.random.fold_in(key, i), shp)

    tree = {n: draw(i, s) for i, (n, s) in enumerate(sorted(shapes.items()))}
    return tree, stacked


def _rand_like(tree, seed: int):
    """Fresh values, same structure/shapes (pseudo-gradient for `tree`)."""
    key = jax.random.PRNGKey(seed * 7919 + 13)
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(treedef, [
        jax.random.normal(jax.random.fold_in(key, i), x.shape)
        for i, x in enumerate(leaves)])


def _tree_close(a, b, **tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), **tol)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.0, 12.0, allow_nan=False))
def test_packed_equals_per_leaf_every_method(seed, tau):
    """(a) per-leaf reference <-> packed-path equivalence for EVERY
    registered method, over random shapes and stacked axes."""
    params, stacked = _rand_tree(seed % 10_000)
    delta = _rand_like(params, seed % 10_000)
    mom = jax.tree.map(lambda x: -0.3 * x + 0.1, delta)
    layout = packing.build_layout(params, stacked)
    pbuf = packing.pack(layout, params)
    mbuf = packing.pack(layout, mom)
    for m in M.all_methods():
        state = init_outer_state(
            params, with_aux=m.uses_buffer)._replace(momentum=mom)
        abuf = packing.zeros(layout) if m.uses_buffer else None
        for phase in (0, max(m.buffer_period - 1, 0)):
            ref = apply_arrival(state, delta, method=m.name, outer_lr=0.7,
                                mu=0.9, h=H, rho=0.447, tau=tau,
                                stacked_axes=stacked, phase=phase)
            out = apply_arrival_packed(pbuf, mbuf, delta, layout,
                                       method=m.name, outer_lr=0.7, mu=0.9,
                                       h=H, rho=0.447, tau=tau, abuf=abuf,
                                       phase=phase)
            if m.uses_buffer:
                p2, m2, b2 = out
                _tree_close(ref.aux,
                            packing.unpack(layout, b2, jnp.float32),
                            rtol=3e-5, atol=3e-5)
            else:
                p2, m2 = out
            _tree_close(ref.params, packing.unpack(layout, p2),
                        rtol=3e-5, atol=3e-5)
            _tree_close(ref.momentum,
                        packing.unpack(layout, m2, jnp.float32),
                        rtol=3e-5, atol=3e-5)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.0, 12.0, allow_nan=False))
def test_decay_collapse_identity_every_method(seed, tau):
    """(b) apply_arrival(zero delta) == momentum_decay_update for EVERY
    registered method — the identity the dropped-arrival fast path
    assumes (generalizing the old _decay_coeffs)."""
    params, stacked = _rand_tree(seed % 10_000)
    mom = jax.tree.map(lambda x: 0.1 * x, params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    layout = packing.build_layout(params, stacked)
    for m in M.all_methods():
        state = init_outer_state(
            params, with_aux=m.uses_buffer)._replace(momentum=mom)
        for phase in (0, max(m.buffer_period - 1, 0)):
            want = apply_arrival(state, zeros, method=m.name, outer_lr=0.7,
                                 mu=0.9, h=H, rho=0.447, tau=tau,
                                 stacked_axes=stacked, phase=phase)
            got = momentum_decay_update(state, 0.7, 0.9, method=m.name,
                                        rho=0.447, tau=tau, phase=phase)
            _tree_close(want.params, got.params, rtol=1e-6, atol=1e-6)
            _tree_close(want.momentum, got.momentum, rtol=1e-6, atol=1e-6)
            if m.uses_buffer:
                _tree_close(want.aux, got.aux, rtol=1e-6, atol=1e-6)
            # and the packed decay step agrees with the per-leaf one
            pbuf = packing.pack(layout, params)
            mbuf = packing.pack(layout, mom)
            abuf = packing.zeros(layout) if m.uses_buffer else None
            outp = momentum_decay_packed(pbuf, mbuf, 0.7, 0.9,
                                         method=m.name, rho=0.447, tau=tau,
                                         abuf=abuf, phase=phase)
            _tree_close(got.params, packing.unpack(layout, outp[0]),
                        rtol=3e-5, atol=3e-5)
            _tree_close(got.momentum,
                        packing.unpack(layout, outp[1], jnp.float32),
                        rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# New-method semantics
# ---------------------------------------------------------------------------

def test_delayed_nesterov_momentum_refresh_cycle():
    """Momentum is frozen between boundaries, refreshes from the buffer
    average every N arrivals, and the buffer resets."""
    m = M.get("delayed_nesterov")
    n = m.buffer_period
    params = {"w": jnp.ones((6, 4))}
    sv = Synchronizer(params, OuterOptConfig(method="delayed_nesterov",
                                             weight_factor="one"), 1)
    delta = {"w": 0.1 * jnp.ones((6, 4))}
    mom_before = np.asarray(sv.state.momentum["w"])
    np.testing.assert_array_equal(mom_before, 0.0)
    for i in range(n - 1):
        sv.on_arrival(jax.tree.map(jnp.copy, delta), s_i=sv.t, worker_id=0)
        # momentum still frozen at zero; buffer accumulating
        np.testing.assert_allclose(np.asarray(sv.state.momentum["w"]), 0.0)
        np.testing.assert_allclose(np.asarray(sv.state.aux["w"]),
                                   0.1 * (i + 1), rtol=1e-6)
    sv.on_arrival(jax.tree.map(jnp.copy, delta), s_i=sv.t, worker_id=0)
    # boundary: m = mu*0 + (1-mu) * (n * 0.1)/n ; buffer reset
    np.testing.assert_allclose(np.asarray(sv.state.momentum["w"]),
                               (1 - 0.9) * 0.1, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sv.state.aux["w"]), 0.0,
                               atol=1e-7)


def test_delayed_nesterov_trajectory_packed_matches_per_leaf():
    params = {"a": jax.random.normal(jax.random.PRNGKey(0), (40, 30)),
              "b": jax.random.normal(jax.random.PRNGKey(1), (129,))}
    cfg = OuterOptConfig(method="delayed_nesterov", drop_stale_after=2)
    svA = Synchronizer(jax.tree.map(jnp.copy, params), cfg, 3, packed=True)
    svB = Synchronizer(jax.tree.map(jnp.copy, params), cfg, 3, packed=False)
    for i in range(9):
        delta = jax.tree.map(
            lambda x: 0.01 * jax.random.normal(jax.random.PRNGKey(i),
                                               x.shape), params)
        ra = svA.on_arrival(jax.tree.map(jnp.copy, delta),
                            s_i=max(0, svA.t - 3), worker_id=0)
        rb = svB.on_arrival(jax.tree.map(jnp.copy, delta),
                            s_i=max(0, svB.t - 3), worker_id=0)
        assert ra.dropped == rb.dropped
    assert any(r.dropped for r in svA.records)      # decay path exercised
    _tree_close(svA.state.params, svB.state.params, rtol=3e-5, atol=3e-5)
    _tree_close(svA.state.momentum, svB.state.momentum,
                rtol=3e-5, atol=3e-5)
    _tree_close(svA.state.aux, svB.state.aux, rtol=3e-5, atol=3e-5)


def test_delayed_nesterov_state_roundtrip_carries_buffer():
    """Checkpoint semantics: the accumulator buffer survives the state
    property/setter round-trip bit-exactly."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(2), (33,))}
    sv = Synchronizer(params, OuterOptConfig(method="delayed_nesterov"), 2)
    sv.on_arrival({"w": 0.1 * jnp.ones((33,))}, s_i=0, worker_id=0)
    snap = sv.state
    assert snap.aux is not None
    sv2 = Synchronizer(params, OuterOptConfig(method="delayed_nesterov"), 2)
    sv2.state = snap
    assert sv2.t == sv.t == 1
    np.testing.assert_array_equal(np.asarray(sv2.state.aux["w"]),
                                  np.asarray(snap.aux["w"]))


def test_fedbuff_applies_buffer_average_every_k_arrivals():
    """FedBuff semantics: nothing moves between boundaries (params AND
    momentum frozen, buffer accumulating); every K-th arrival applies
    the buffer average through one Nesterov step and resets the buffer."""
    m = M.get("fedbuff")
    k = m.buffer_period
    params = {"w": jnp.ones((6, 4))}
    sv = Synchronizer(params, OuterOptConfig(method="fedbuff",
                                             weight_factor="one"), 1)
    delta = {"w": 0.1 * jnp.ones((6, 4))}
    for i in range(k - 1):
        sv.on_arrival(jax.tree.map(jnp.copy, delta), s_i=sv.t, worker_id=0)
        np.testing.assert_allclose(np.asarray(sv.state.params["w"]), 1.0,
                                   rtol=1e-6)          # params frozen
        np.testing.assert_allclose(np.asarray(sv.state.momentum["w"]), 0.0)
        np.testing.assert_allclose(np.asarray(sv.state.aux["w"]),
                                   0.1 * (i + 1), rtol=1e-6)
    sv.on_arrival(jax.tree.map(jnp.copy, delta), s_i=sv.t, worker_id=0)
    # boundary: gbar = K*0.1/K = 0.1; m' = (1-mu)*gbar; p' = p - eta*(gbar
    # + mu*m'); buffer reset
    mu, eta = 0.9, m.outer_lr
    m_new = (1 - mu) * 0.1
    np.testing.assert_allclose(np.asarray(sv.state.momentum["w"]), m_new,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sv.state.params["w"]),
                               1.0 - eta * (0.1 + mu * m_new), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sv.state.aux["w"]), 0.0,
                               atol=1e-7)


def test_fedbuff_trajectory_packed_matches_per_leaf():
    params = {"a": jax.random.normal(jax.random.PRNGKey(3), (32, 20)),
              "b": jax.random.normal(jax.random.PRNGKey(4), (77,))}
    cfg = OuterOptConfig(method="fedbuff")
    svA = Synchronizer(jax.tree.map(jnp.copy, params), cfg, 3, packed=True)
    svB = Synchronizer(jax.tree.map(jnp.copy, params), cfg, 3, packed=False)
    for i in range(9):
        delta = jax.tree.map(
            lambda x: 0.02 * jax.random.normal(jax.random.PRNGKey(40 + i),
                                               x.shape), params)
        svA.on_arrival(jax.tree.map(jnp.copy, delta),
                       s_i=max(0, svA.t - 2), worker_id=0)
        svB.on_arrival(jax.tree.map(jnp.copy, delta),
                       s_i=max(0, svB.t - 2), worker_id=0)
    _tree_close(svA.state.params, svB.state.params, rtol=3e-5, atol=3e-5)
    _tree_close(svA.state.momentum, svB.state.momentum,
                rtol=3e-5, atol=3e-5)
    _tree_close(svA.state.aux, svB.state.aux, rtol=3e-5, atol=3e-5)


def test_poly_stale_damps_polynomially_with_staleness():
    m = M.get("poly_stale")
    delta = {"w": jnp.asarray([1.0, -2.0, 0.5])}
    mom = {"w": jnp.asarray([0.3, 0.3, 0.3])}

    def norm_at(tau):
        ctx = M.ArrivalCtx(outer_lr=0.07, mu=0.9, h=H,
                           tau=jnp.asarray(tau, jnp.float32))
        g = m.correct(m, ctx, delta, mom)
        return float(jnp.linalg.norm(g["w"]))

    base = float(jnp.linalg.norm(delta["w"]))
    np.testing.assert_allclose(norm_at(0.0), base, rtol=1e-6)   # tau=0: id
    for tau in (1.0, 3.0, 8.0):
        np.testing.assert_allclose(norm_at(tau),
                                   base * (1.0 + tau) ** -m.stale_alpha,
                                   rtol=1e-5)
    assert norm_at(8.0) < norm_at(1.0) < base


def test_dcasgd_reduces_to_nesterov_at_zero_staleness():
    params, stacked = _rand_tree(5)
    delta = _rand_like(params, 6)
    mom = jax.tree.map(lambda x: 0.2 * x, delta)
    state = init_outer_state(params)._replace(momentum=mom)
    a = apply_arrival(state, delta, method="dcasgd", outer_lr=0.7, mu=0.9,
                      h=H, tau=0.0, stacked_axes=stacked)
    b = apply_arrival(state, delta, method="nesterov", outer_lr=0.7, mu=0.9,
                      h=H, tau=0.0, stacked_axes=stacked)
    _tree_close(a.params, b.params, rtol=1e-6, atol=1e-6)


def test_dcasgd_compensation_scales_with_staleness():
    """The Taylor term actually bites: larger tau moves the corrected
    gradient further from the raw delta, saturating at tau_clip."""
    m = M.get("dcasgd")
    delta = {"w": jnp.asarray([0.5, -0.5, 1.0])}
    mom = {"w": jnp.asarray([1.0, 1.0, -1.0])}

    def gap(tau):
        ctx = M.ArrivalCtx(outer_lr=0.7, mu=0.9, h=H, tau=jnp.asarray(tau))
        g = m.correct(m, ctx, delta, mom)
        return float(jnp.linalg.norm(g["w"] - delta["w"]))

    assert gap(0.0) == 0.0
    assert gap(2.0) < gap(8.0)
    np.testing.assert_allclose(gap(m.tau_clip), gap(m.tau_clip * 5),
                               rtol=1e-6)
