"""Distribution-layer tests on a small fake-device mesh (8 devices):
sharding rule sanity, multipod train-step pod independence, and the
HeLoCo outer exchange (sync/async + int8) vs the single-host reference."""
import os
import subprocess
import sys

import pytest

# These tests need multiple fake devices; run the real checks in a
# subprocess so the main pytest process keeps its single-device view.

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced
from repro.configs.base import HeLoCoConfig, InnerOptConfig
from repro.dist import sharding as shd
from repro.dist.steps import (init_train_state, make_multipod_train_step,
                              make_outer_exchange, make_train_step)
from repro.launch.mesh import make_test_mesh
from repro.core.heloco import OuterState, block_correct, outer_update, lookahead_init
from repro.models import build_model

cfg = dataclasses.replace(reduced(get_config("qwen2-7b")),
                          act_batch_axes=("data",))
mesh = make_test_mesh(multi_pod=True)   # (pod=2, data=2, model=2)
axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
pspecs = shd.param_specs(params, axis_sizes=axis_sizes)

# ---- multipod train step: pods with identical params+batch stay identical,
# different batches diverge (proves per-pod independence = no cross-pod psum)
inner = InnerOptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
step = make_multipod_train_step(cfg, inner, mesh, grad_accum=1, q_chunk=16,
                                param_pspecs=pspecs)
state = init_train_state(params)
stack = lambda t: jax.tree.map(lambda x: jnp.stack([x, x]), t)
state2 = stack(state)
tok = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 16), 0, cfg.vocab_size)
batch_same = {"tokens": tok[:1].repeat(2, 0), "labels": tok[:1].repeat(2, 0)}
batch_diff = {"tokens": tok, "labels": tok}
with jax.set_mesh(mesh):
    ns, loss = jax.jit(step)(state2, batch_same)
    leaf = jax.tree.leaves(ns.params)[0]
    np.testing.assert_array_equal(np.asarray(leaf[0]), np.asarray(leaf[1]))
    nd, loss2 = jax.jit(step)(stack(state), batch_diff)
    leafd = jax.tree.leaves(nd.params)[-1]
    assert not np.allclose(np.asarray(leafd[0]), np.asarray(leafd[1])), \
        "pods with different data must diverge"
print("MULTIPOD_OK")

# ---- outer exchange vs single-host reference
h = HeLoCoConfig()
stacked = shd.stacked_axes_tree(params)
mom = jax.tree.map(lambda x: 0.01 * jnp.ones_like(x, jnp.float32), params)
wp = jax.tree.map(lambda x: jnp.stack([x - 0.05, x + 0.02]), params)
fn = make_outer_exchange(cfg, mesh, h=h, outer_lr=0.7, mu=0.9,
                         method="heloco", arriving_pod=1,
                         stacked_axes=stacked)
with jax.set_mesh(mesh):
    new_p, new_m, bar = jax.jit(fn)(params, mom, wp)
# reference: delta from pod 1 only
delta_ref = jax.tree.map(
    lambda a, b: a.astype(jnp.float32) - b[1].astype(jnp.float32), params, wp)
g_ref = block_correct(delta_ref, mom, h, stacked_axes=stacked)
st_ref = outer_update(OuterState(params, mom, jnp.zeros((), jnp.int32)),
                      g_ref, 0.7, 0.9)
for a, b in zip(jax.tree.leaves(new_p), jax.tree.leaves(st_ref.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)
bar_ref = lookahead_init(st_ref, 0.7, 0.9)
for a, b in zip(jax.tree.leaves(bar), jax.tree.leaves(bar_ref)):
    np.testing.assert_allclose(np.asarray(a)[0], np.asarray(b), rtol=2e-5,
                               atol=2e-5)
print("EXCHANGE_OK")

# ---- int8-compressed exchange: close to uncompressed, not exact
fn8 = make_outer_exchange(cfg, mesh, h=h, outer_lr=0.7, mu=0.9,
                          method="heloco", arriving_pod=1,
                          stacked_axes=stacked, compress_int8=True)
with jax.set_mesh(mesh):
    p8, m8, _ = jax.jit(fn8)(params, mom, wp)
num = den = 0.0
for a, b in zip(jax.tree.leaves(p8), jax.tree.leaves(new_p)):
    num += float(jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32))**2))
    den += float(jnp.sum(b.astype(jnp.float32)**2))
rel = (num / max(den, 1e-12)) ** 0.5
assert rel < 0.02, f"int8 exchange too lossy: {rel}"
print("INT8_OK", rel)
"""


def test_dist_semantics_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "MULTIPOD_OK" in out.stdout, out.stdout + out.stderr
    assert "EXCHANGE_OK" in out.stdout, out.stdout + out.stderr
    assert "INT8_OK" in out.stdout, out.stdout + out.stderr


def test_sharding_rules_unit():
    """Pure-python rule checks (no devices needed)."""
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import spec_for
    sizes = {"data": 16, "model": 16}
    # divisible heads -> head TP
    assert spec_for("blocks/attn/wq", (28, 4096, 32, 128), data_axis="data",
                    model_axis="model", axis_sizes=sizes) == \
        P(None, "data", "model", None)
    # non-divisible heads -> head_dim TP fallback
    assert spec_for("blocks/attn/wq", (28, 3584, 28, 128), data_axis="data",
                    model_axis="model", axis_sizes=sizes) == \
        P(None, "data", None, "model")
    # vocab not divisible -> replicate vocab dim
    assert spec_for("embed/tok", (49155, 4096), data_axis="data",
                    model_axis="model", axis_sizes=sizes) == P(None, "data")
    # norm scale -> fully replicated
    assert spec_for("blocks/norm1/scale", (28, 4096), data_axis="data",
                    model_axis="model", axis_sizes=sizes) == P(None, None)
    # MoE experts over model axis
    assert spec_for("blocks/moe/w_gate", (24, 32, 1024, 512),
                    data_axis="data", model_axis="model",
                    axis_sizes=sizes) == P(None, "model", "data", None)


def test_cache_specs_unit():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import cache_specs
    sizes = {"data": 16, "model": 16}
    caches = {"k": jax.ShapeDtypeStruct((28, 128, 32768, 4, 128), jnp.bfloat16),
              "v": jax.ShapeDtypeStruct((28, 128, 32768, 4, 128), jnp.bfloat16)}
    # batch-sharded decode: B over data; kv=4 < 16 -> head_dim over model
    specs = cache_specs(caches, batch_sharded=True, axis_sizes=sizes)
    assert specs["k"] == P(None, "data", None, None, "model")
    # context-parallel long decode: S over data
    specs = cache_specs(caches, batch_sharded=False, axis_sizes=sizes)
    assert specs["k"] == P(None, None, "data", None, "model")
