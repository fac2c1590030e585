"""End-to-end behaviour tests for the HeLoCo system: the paper's headline
qualitative claims on a tiny model, plus config registry integrity."""
import os

import numpy as np
import pytest

from repro.configs import ARCHS, ASSIGNED, SHAPES, cells, get_config, reduced


def test_registry_has_all_assigned_archs():
    expected = {
        "zamba2-2.7b", "qwen2-7b", "granite-3-8b", "command-r-35b",
        "starcoder2-15b", "granite-moe-1b-a400m", "llama4-scout-17b-a16e",
        "hubert-xlarge", "xlstm-125m", "paligemma-3b",
    }
    assert expected == set(ASSIGNED)
    assert "tinygpt-15m" in ARCHS


def test_exact_assigned_configs():
    q = get_config("qwen2-7b")
    assert (q.n_layers, q.d_model, q.n_heads, q.n_kv_heads, q.d_ff,
            q.vocab_size) == (28, 3584, 28, 4, 18944, 152064)
    assert q.qkv_bias
    c = get_config("command-r-35b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (40, 8192, 64, 8, 22528, 256000)
    m = get_config("granite-moe-1b-a400m")
    assert (m.moe.n_experts, m.moe.top_k) == (32, 8)
    l4 = get_config("llama4-scout-17b-a16e")
    assert (l4.moe.n_experts, l4.moe.top_k) == (16, 1)
    z = get_config("zamba2-2.7b")
    assert (z.n_layers, z.ssm.d_state) == (54, 64)
    x = get_config("xlstm-125m")
    assert (x.n_layers, x.d_ff) == (12, 0)
    h = get_config("hubert-xlarge")
    assert h.encoder_only and h.vocab_size == 504
    p = get_config("paligemma-3b")
    assert p.n_kv_heads == 1 and p.frontend.kind == "vision"


def test_cells_inventory():
    rows = list(cells())
    assert len(rows) == 40
    runnable = [r for r in rows if r[2]]
    skipped = [r for r in rows if not r[2]]
    assert len(runnable) == 31
    # skips: 8 full-attention long_500k + hubert decode_32k
    assert len(skipped) == 9
    for m, s, ok, why in skipped:
        assert why, (m.name, s.name)


def test_reduced_configs_are_small():
    for arch in ASSIGNED:
        r = reduced(get_config(arch))
        assert r.d_model <= 64 and r.n_layers <= 4 and r.vocab_size <= 128


@pytest.mark.slow
def test_heloco_beats_async_nesterov_under_staleness():
    """Paper's central claim, minimal form: with heterogeneous paces and
    non-IID data, async HeLoCo reaches lower validation loss than plain
    async Nesterov at the same outer-step (token) budget."""
    from benchmarks.common import base_run, run_cached
    paces = (1.0, 2.0, 6.0, 6.0)
    rh = run_cached("sys_heloco", base_run(
        paces, method="async-heloco", non_iid=True, outer_steps=20,
        inner_steps=6, seed=3))
    rn = run_cached("sys_nesterov", base_run(
        paces, method="async-nesterov", non_iid=True, outer_steps=20,
        inner_steps=6, seed=3))
    assert rh["final_loss"] < rn["final_loss"], (rh["final_loss"],
                                                 rn["final_loss"])
    # and training actually learned something
    assert rh["final_loss"] < rh["evals"][0]["mean"]


@pytest.mark.slow
def test_lookahead_init_helps_or_neutral():
    """Eq. 5 look-ahead init should not hurt under staleness (sanity)."""
    import dataclasses
    from benchmarks.common import base_run, run_cached
    paces = (1.0, 1.0, 6.0, 6.0)
    rc_on = base_run(paces, method="async-heloco", non_iid=True,
                     outer_steps=16, inner_steps=6, seed=5)
    rc_off = dataclasses.replace(
        rc_on, outer=dataclasses.replace(rc_on.outer, lookahead_init=False))
    on = run_cached("sys_lookahead_on", rc_on)
    off = run_cached("sys_lookahead_off", rc_off)
    assert on["final_loss"] <= off["final_loss"] + 0.15


def test_compile_cache_dir_is_fixed_or_taken_from_the_environment(
        monkeypatch):
    import jax
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", prev)
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_run_cache_key_names_the_device(monkeypatch):
    """A result cached on one device kind is never served on another."""
    import jax
    from benchmarks import common

    class Fake:
        platform, device_kind = "tpu", "TPU v5 lite"

    rc = common.base_run((1.0, 2.0), method="heloco", non_iid=True,
                         outer_steps=2, inner_steps=1)
    here = common._key(rc, 0)
    monkeypatch.setattr(common.jax, "devices", lambda: [Fake()])
    assert common._key(rc, 0) != here
