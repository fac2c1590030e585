"""Data pipeline + inner optimizer unit/property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import InnerOptConfig
from repro.data.synthetic import (
    ShardSampler, eval_batches, make_language_specs, sample_tokens,
)
from repro.optim.adamw import (
    AdamState, adamw_update, clip_by_global_norm, global_norm, init_adam,
)
from repro.optim.schedules import cosine_warmup


# ------------------------------- data -------------------------------------

def test_shards_are_deterministic_and_distinct():
    specs = make_language_specs(512, n_langs=5, seed=0)
    s0 = ShardSampler(specs, 0, batch=4, seq=32, seed=7)
    s0b = ShardSampler(specs, 0, batch=4, seq=32, seed=7)
    s1 = ShardSampler(specs, 1, batch=4, seq=32, seed=7)
    a, b, c = s0.sample(3), s0b.sample(3), s1.sample(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])  # deterministic
    assert not np.array_equal(a["tokens"], c["tokens"])      # non-IID differs


def test_language_token_ranges_disjoint():
    specs = make_language_specs(512, n_langs=5, seed=0)
    rng = np.random.default_rng(0)
    toks0 = sample_tokens(specs[0], 8, 128, rng)
    toks1 = sample_tokens(specs[1], 8, 128, rng)
    shared_hi = specs[0].shared_hi
    own0 = toks0[toks0 >= shared_hi]
    own1 = toks1[toks1 >= shared_hi]
    assert own0.max() < specs[1].lo or own0.min() >= specs[1].hi
    assert len(np.intersect1d(np.unique(own0), np.unique(own1))) == 0


def test_labels_are_shifted_tokens():
    specs = make_language_specs(256, n_langs=2, seed=1)
    s = ShardSampler(specs, 0, batch=2, seq=16, seed=3)
    b = s.sample(0)
    assert b["tokens"].shape == (2, 16)
    assert b["labels"].shape == (2, 16)


def test_eval_batches_cover_all_langs():
    specs = make_language_specs(512, n_langs=5, seed=0)
    evs = eval_batches(specs, 4, 32)
    assert len(evs) == 5
    assert len({e["lang"] for e in evs}) == 5


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 4))
def test_sampler_tokens_in_vocab(step, batch):
    specs = make_language_specs(128, n_langs=3, seed=2)
    s = ShardSampler(specs, step % 3, batch=batch, seq=8, seed=11)
    b = s.sample(step)
    assert b["tokens"].min() >= 0
    assert b["tokens"].max() < 128


# ------------------------------- optim ------------------------------------

def test_adamw_decreases_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = init_adam(params)
    cfg = InnerOptConfig(lr=0.1, warmup_steps=0, total_steps=100,
                         weight_decay=0.0, schedule="constant")
    for _ in range(200):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt = adamw_update(params, grads, opt, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_grad_clip_bounds_norm():
    g = {"a": jnp.full((10,), 100.0), "b": jnp.full((5,), -50.0)}
    clipped = clip_by_global_norm(g, 1.0)
    assert float(global_norm(clipped)) <= 1.0 + 1e-5


def test_cosine_schedule_shape():
    lr0 = float(cosine_warmup(0, 1.0, warmup_steps=10, total_steps=100))
    lr_w = float(cosine_warmup(10, 1.0, warmup_steps=10, total_steps=100))
    lr_end = float(cosine_warmup(100, 1.0, warmup_steps=10, total_steps=100))
    assert lr0 == 0.0
    assert lr_w == pytest.approx(1.0)
    assert lr_end == pytest.approx(0.1, rel=1e-3)  # final_frac default


def test_adam_count_increments_and_bias_correction():
    params = {"w": jnp.ones((3,))}
    opt = init_adam(params)
    cfg = InnerOptConfig(lr=0.01, warmup_steps=0, total_steps=10,
                         schedule="constant", weight_decay=0.0)
    g = {"w": jnp.ones((3,))}
    p1, opt = adamw_update(params, g, opt, cfg)
    assert int(opt.count) == 1
    # first Adam step with constant grad ~= lr * sign(g)
    np.testing.assert_allclose(np.asarray(params["w"] - p1["w"]),
                               0.01 * np.ones(3), rtol=1e-3)


# ---------------------------- compression ---------------------------------

def test_error_feedback_converges():
    """With error feedback, repeated compression of a constant signal must
    deliver the full mass over time (unbiasedness over rounds)."""
    from repro.core.compression import roundtrip_with_error_feedback
    target = {"w": jnp.asarray(np.random.default_rng(0).normal(size=512),
                               jnp.float32)}
    ef = None
    delivered = jnp.zeros(512)
    for _ in range(30):
        dec, ef, _ = roundtrip_with_error_feedback(target, ef, "topk", 0.1)
        delivered = delivered + dec["w"]
    avg = delivered / 30
    err = float(jnp.linalg.norm(avg - target["w"]) /
                jnp.linalg.norm(target["w"]))
    assert err < 0.25, err


def test_int8_roundtrip_error_bound():
    from repro.core.compression import compress, decompress
    x = {"w": jnp.linspace(-4.0, 4.0, 1000)}
    c = compress(x, "int8")
    y = decompress(c, x)
    assert float(jnp.abs(y["w"] - x["w"]).max()) <= 4.0 / 127.0 + 1e-6
