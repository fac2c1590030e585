"""Per-kernel validation: sweep shapes/dtypes and assert_allclose against
the pure-jnp oracles in repro/kernels/ref.py. Kernels run in interpret
mode on CPU with a single-step grid (see kernels.tiling.row_tile); the
multi-step TPU index maps are exercised via the explicit ``rows=``
override (test_packed.py::test_multi_step_grid_matches_single_step)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import HeLoCoConfig
from repro.kernels import ops
from repro.kernels.ref import (
    ref_dequantize, ref_heloco_correct, ref_outer_update, ref_quantize,
)

H = HeLoCoConfig()

SHAPES = [(7,), (128,), (129,), (4, 33), (256, 128), (3, 5, 64), (1000, 130)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
           dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_heloco_correct_kernel(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31))
    u = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    v = jax.random.normal(k2, shape, jnp.float32).astype(dtype)
    got = ops.heloco_correct_block(u, v, H, interpret=True)
    want = ref_heloco_correct(u, v, H)
    assert got.shape == shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("case", ["aligned", "anti", "weak", "zero_u", "zero_v"])
def test_heloco_correct_kernel_branches(case):
    base = jnp.arange(1.0, 513.0)
    u, v = {
        "aligned": (base, 2 * base),
        "anti": (base, -base),
        "weak": (base, jnp.roll(base, 256) - base.mean()),
        "zero_u": (jnp.zeros_like(base), base),
        "zero_v": (base, jnp.zeros_like(base)),
    }[case]
    got = ops.heloco_correct_block(u, v, H, interpret=True)
    want = ref_heloco_correct(u, v, H)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 5000), st.integers(0, 2**31 - 1))
def test_heloco_correct_kernel_property(n, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    u = jax.random.normal(k1, (n,))
    v = jax.random.normal(k2, (n,))
    got = ops.heloco_correct_block(u, v, H, interpret=True)
    want = ref_heloco_correct(u, v, H)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_outer_update_kernel(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    p = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    m = jax.random.normal(ks[1], shape, jnp.float32)
    g = jax.random.normal(ks[2], shape, jnp.float32)
    got_p, got_m = ops.outer_update_block(p, m, g, 0.7, 0.9, 0.447,
                                          interpret=True)
    want_p, want_m = ref_outer_update(p, m, g, 0.7, 0.9, 0.447)
    assert got_p.dtype == p.dtype and got_m.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_p, np.float32),
                               np.asarray(want_p, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
def test_quantize_roundtrip_kernel(shape):
    x = jax.random.normal(jax.random.PRNGKey(3), shape) * 5.0
    q2d, scale, _ = ops.quantize_block(x, interpret=True)
    assert q2d.dtype == jnp.int8
    got = ops.dequantize_block(q2d, scale, shape, interpret=True)
    want = ref_dequantize(*ref_quantize(x)).reshape(shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # quantization error bounded by scale/2 per element
    err = np.abs(np.asarray(got) - np.asarray(x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_kernel_path_equals_core_in_block_correct():
    """core.block_correct(use_kernel=True) must match the jnp path."""
    from repro.core.heloco import block_correct
    key = jax.random.PRNGKey(0)
    delta = {"a": jax.random.normal(key, (40, 30)),
             "b": {"c": jax.random.normal(jax.random.PRNGKey(1), (17,))}}
    mom = jax.tree.map(lambda x: -x + 0.3, delta)
    a = block_correct(delta, mom, H, use_kernel=False)
    b = block_correct(delta, mom, H, use_kernel=True)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Pallas flash-attention forward kernel vs naive softmax oracle
# ---------------------------------------------------------------------------

def _naive_attn(q, k, v, causal):
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * q.shape[-1] ** -0.5
    if causal:
        qi = jnp.arange(q.shape[1])[:, None]
        ki = jnp.arange(k.shape[1])[None, :]
        s = jnp.where(ki <= qi, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(q.dtype), v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 128, 64), (1, 256, 128), (3, 512, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_kernel(causal, shape, dtype):
    from repro.kernels.flash_attention import flash_attention_fwd
    bh, s, d = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], shape, jnp.float32).astype(dtype)
    got = flash_attention_fwd(q, k, v, causal=causal, q_chunk=64,
                              kv_chunk=128, interpret=True)
    want = _naive_attn(q.astype(jnp.float32), k.astype(jnp.float32),
                       v.astype(jnp.float32), causal)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_flash_fwd_kernel_rectangular():
    """Sq != Skv (prefill-continuation shape) + uneven chunking."""
    from repro.kernels.flash_attention import flash_attention_fwd
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 128, 64))
    k = jax.random.normal(ks[1], (2, 384, 64))
    v = jax.random.normal(ks[2], (2, 384, 64))
    got = flash_attention_fwd(q, k, v, causal=False, q_chunk=32,
                              kv_chunk=128, interpret=True)
    want = _naive_attn(q, k, v, False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
