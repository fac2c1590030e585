"""Unit + property tests for the HeLoCo core math (paper Eqs. 5-19 and the
Appendix A.2 lemma invariants)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import HeLoCoConfig
from repro.core.heloco import (
    OuterState, apply_arrival, block_correct, correct_block, init_outer_state,
    lookahead_init, outer_update,
)

H = HeLoCoConfig()  # paper defaults: c_ok=0.2, k_s=0.5, k_d=1.0, kappa=3, beta_max=0.5


def _vec(xs):
    return jnp.asarray(xs, jnp.float32)


# ---------------------------------------------------------------------------
# Branch behaviour (Alg. 2)
# ---------------------------------------------------------------------------

def test_aligned_block_unchanged():
    u = _vec([1.0, 2.0, 3.0])
    v = 0.5 * u  # cosine = 1 >= c_ok
    out = correct_block(u, v, H)
    np.testing.assert_allclose(np.asarray(out), np.asarray(u), rtol=1e-6)


def test_degenerate_blocks_pass_through():
    u = _vec([1.0, -1.0, 2.0])
    z = jnp.zeros(3)
    np.testing.assert_allclose(np.asarray(correct_block(u, z, H)),
                               np.asarray(u))
    np.testing.assert_allclose(np.asarray(correct_block(z, u, H)),
                               np.asarray(z))


def test_anti_aligned_matches_eq10():
    u = _vec([1.0, 0.0])
    v = _vec([-2.0, 0.0])          # cosine = -1
    nu, nv = 1.0, 2.0
    c = -1.0
    conf = nu / (nu + H.kappa * nv + H.eps)
    beta = min(H.k_s * (-c) * conf, H.beta_max)
    expected = np.array([1.0, 0.0]) - beta * c * nu * np.array([-1.0, 0.0])
    out = np.asarray(correct_block(u, v, H))
    np.testing.assert_allclose(out, expected, rtol=1e-6)
    # anti-momentum component shrank (less negative along v_hat)
    assert out @ np.array([-1.0, 0.0]) > float(u @ _vec([-1.0, 0.0]))


def test_weak_aligned_preserves_norm_and_rotates():
    u = _vec([1.0, 0.0])
    v = _vec([0.1, 1.0])           # small positive cosine < c_ok
    out = np.asarray(correct_block(u, v, H))
    np.testing.assert_allclose(np.linalg.norm(out), 1.0, rtol=1e-5)
    v_hat = np.asarray(v) / np.linalg.norm(v)
    c_before = float(u @ v_hat)
    c_after = float(out @ v_hat)
    assert 0 <= c_before < H.c_ok
    assert c_after >= c_before  # rotated toward momentum


# ---------------------------------------------------------------------------
# A.2 lemma invariants (property-based)
# ---------------------------------------------------------------------------

@settings(max_examples=200, deadline=None)
@given(st.lists(st.floats(-10, 10, allow_nan=False), min_size=2, max_size=16),
       st.lists(st.floats(-10, 10, allow_nan=False), min_size=2, max_size=16),
       st.floats(0.0, 1.0), st.floats(0.01, 5.0), st.floats(0.01, 5.0))
def test_lemma_invariants(us, vs, c_ok, k_s, k_d):
    n = min(len(us), len(vs))
    u = _vec(us[:n])
    v = _vec(vs[:n])
    h = HeLoCoConfig(c_ok=c_ok, k_s=k_s, k_d=k_d, beta_max=1.0)
    out = correct_block(u, v, h)
    nu = float(jnp.linalg.norm(u))
    nv = float(jnp.linalg.norm(v))
    if nu < h.eps or nv < h.eps:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(u))
        return
    v_hat = np.asarray(v) / nv
    # (i) signed component along momentum never decreases
    assert float(np.asarray(out) @ v_hat) >= float(np.asarray(u) @ v_hat) - 1e-4 * max(nu, 1)
    # (ii) norm never amplified
    assert float(jnp.linalg.norm(out)) <= nu * (1 + 1e-5) + 1e-6


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 64))
def test_correction_invariants_gaussian(seed, dim):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    u = jax.random.normal(k1, (dim,))
    v = jax.random.normal(k2, (dim,))
    out = correct_block(u, v, H)
    v_hat = v / jnp.linalg.norm(v)
    assert float(out @ v_hat) >= float(u @ v_hat) - 1e-4
    assert float(jnp.linalg.norm(out)) <= float(jnp.linalg.norm(u)) * (1 + 1e-5)


# ---------------------------------------------------------------------------
# Pytree-level correction
# ---------------------------------------------------------------------------

def test_block_correct_treats_each_tensor_separately():
    delta = {"a": _vec([1.0, 0.0]), "b": _vec([0.0, 1.0])}
    mom = {"a": _vec([1.0, 0.0]), "b": _vec([0.0, -1.0])}
    out = block_correct(delta, mom, H)
    np.testing.assert_allclose(np.asarray(out["a"]), [1.0, 0.0])  # aligned: kept
    # b is anti-aligned: corrected, not equal to input
    assert not np.allclose(np.asarray(out["b"]), [0.0, 1.0])


def test_block_correct_stacked_axes_matches_per_layer():
    key = jax.random.PRNGKey(0)
    d = jax.random.normal(key, (3, 4, 5))      # 3 stacked layers
    m = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 5))
    stacked = block_correct({"w": d}, {"w": m}, H, stacked_axes={"w": 1})["w"]
    per = jnp.stack([correct_block(d[i], m[i], H) for i in range(3)])
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(per), rtol=1e-6)
    # and WITHOUT stacked_axes the result differs (flattened as one block)
    flat = block_correct({"w": d}, {"w": m}, H)["w"]
    assert not np.allclose(np.asarray(flat), np.asarray(per), atol=1e-6)


# ---------------------------------------------------------------------------
# Outer update + look-ahead (Eqs. 5, 17-19)
# ---------------------------------------------------------------------------

def test_outer_update_matches_equations():
    params = {"w": _vec([1.0, 2.0])}
    state = init_outer_state(params)
    state = state._replace(momentum={"w": _vec([0.5, -0.5])})
    g = {"w": _vec([0.1, 0.2])}
    mu, eta, rho = 0.9, 0.7, 1.0
    new = outer_update(state, g, eta, mu, rho)
    m_exp = mu * np.array([0.5, -0.5]) + (1 - mu) * np.array([0.1, 0.2])
    p_exp = np.array([1.0, 2.0]) - eta * (np.array([0.1, 0.2]) + mu * m_exp)
    np.testing.assert_allclose(np.asarray(new.momentum["w"]), m_exp, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new.params["w"]), p_exp, rtol=1e-6)
    assert int(new.step) == 1


def test_lookahead_init_eq5():
    params = {"w": _vec([1.0, 2.0])}
    state = init_outer_state(params)._replace(momentum={"w": _vec([1.0, -1.0])})
    bar = lookahead_init(state, outer_lr=0.7, mu=0.9)
    np.testing.assert_allclose(np.asarray(bar["w"]),
                               np.array([1.0, 2.0]) - 0.7 * 0.9 * np.array([1.0, -1.0]),
                               rtol=1e-6)


@pytest.mark.parametrize("method", ["heloco", "mla", "nesterov"])
def test_apply_arrival_runs(method):
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 4))}
    state = init_outer_state(params)
    delta = {"w": jax.random.normal(jax.random.PRNGKey(1), (8, 4))}
    new = apply_arrival(state, delta, method=method, outer_lr=0.7, mu=0.9,
                        h=H, tau=3.0)
    assert int(new.step) == 1
    assert np.all(np.isfinite(np.asarray(new.params["w"])))
    assert not np.allclose(np.asarray(new.params["w"]),
                           np.asarray(params["w"]))


def test_heloco_equals_nesterov_when_aligned():
    """If every block is perfectly aligned with momentum, HeLoCo reduces to
    plain async Nesterov (blocks kept unchanged)."""
    params = {"w": _vec([1.0, 2.0, 3.0])}
    mom = {"w": _vec([0.2, 0.4, 0.6])}
    delta = {"w": _vec([0.1, 0.2, 0.3])}   # parallel to momentum
    state = init_outer_state(params)._replace(momentum=mom)
    a = apply_arrival(state, delta, method="heloco", outer_lr=0.7, mu=0.9, h=H)
    b = apply_arrival(state, delta, method="nesterov", outer_lr=0.7, mu=0.9, h=H)
    np.testing.assert_allclose(np.asarray(a.params["w"]),
                               np.asarray(b.params["w"]), rtol=1e-6)
