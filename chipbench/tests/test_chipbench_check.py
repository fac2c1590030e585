"""The correctness check on the CPU at a tiny size: a sound run of the
harness passes it, and a run with the timed path broken underneath
fails it, once for each fault a one-chip training cell can have."""
import json
import os
import shutil
import time

import numpy as np
import pytest

from chipbench import harness
from chipbench.cells import HERE, ROOT, load_cell

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2147483659


def _tiny(tmp_path_factory, name: str, **job_changes):
    """A cell of the benchmark at a tiny width, with its own limits."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cell = load_cell(name, root=str(root))
    cfg_path = root / "chipbench/configs" / (name.split(".")[0] + ".json")
    cfg = json.loads(cfg_path.read_text())
    cfg["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                        head_dim=16, d_ff=128, vocab_size=256)
    cfg_path.write_text(json.dumps(cfg))
    job_path = root / "chipbench/traffic" / (name.split(".", 1)[1] + ".json")
    job = dict(cell.job, batch_size=2, seq_len=16, eval_batch=2,
               **job_changes)
    job_path.write_text(json.dumps(job))
    return load_cell(name, root=str(root))


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """tinygpt15m.paper-k1 at a tiny width."""
    return _tiny(tmp_path_factory, "tinygpt15m.paper-k1")


@pytest.fixture(scope="module")
def tiny_int8_cell(tmp_path_factory):
    """tinygpt15m.w16-k4-int8 at a tiny width: every commit is a fused
    flush of four int8 arrivals."""
    return _tiny(tmp_path_factory, "tinygpt15m.w16-k4-int8")


def _run(cell):
    return harness.run_cell(cell, SEED, 0.3, False, time.perf_counter(),
                            PEAKS, say=lambda s: None)


def test_a_sound_run_is_correct(tiny_cell):
    out = _run(tiny_cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(tiny_cell.limits)


def _state_unchanged(monkeypatch):
    from repro.async_engine.server import Synchronizer

    def step(self, delta, rho, tau):
        self._step += 1
        self._state_cache = None

    def step_multi(self, deltas, rhos, taus):
        self._step += len(deltas)
        self._state_cache = None
    monkeypatch.setattr(Synchronizer, "_step_update", step)
    monkeypatch.setattr(Synchronizer, "_step_update_multi", step_multi)


def _half_batch(monkeypatch):
    from repro.data.synthetic import ShardSampler
    sample = ShardSampler.sample

    def half(self, step):
        b = sample(self, step)
        return {k: v[: len(v) // 2] for k, v in b.items()}
    monkeypatch.setattr(ShardSampler, "sample", half)


def _delta_altered(monkeypatch):
    import jax
    from repro.async_engine import engine
    made = engine.pseudo_gradient

    def altered(theta_init, theta_final):
        leaves, tree = jax.tree.flatten(made(theta_init, theta_final))
        return tree.unflatten([2.0 * leaves[0]] + leaves[1:])
    monkeypatch.setattr(engine, "pseudo_gradient", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _delta_altered],
                         ids=["state_unchanged", "half_batch",
                              "delta_altered"])
def test_a_broken_timed_path_is_not_correct(tiny_cell, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(tiny_cell)
    assert not out["correct"], out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_a_sound_int8_flush_is_correct(tiny_int8_cell):
    out = _run(tiny_int8_cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _delta_altered],
                         ids=["state_unchanged", "half_batch",
                              "delta_altered"])
def test_a_broken_int8_flush_is_not_correct(tiny_int8_cell, monkeypatch,
                                            fault):
    """The same faults under the fused flush of int8 arrivals."""
    fault(monkeypatch)
    out = _run(tiny_int8_cell)
    assert not out["correct"], out["checks"]


def test_the_control_is_not_correct(tiny_cell):
    """The reference in the program's place with fp8 matrix products
    fails the cell's limits; the reference against itself passes."""
    ref = harness.reference_readings(tiny_cell, SEED)
    same = harness.compare(ref, ref)
    assert all(same[k] == 0.0 for k in tiny_cell.limits)
    low = harness.compare(
        harness.reference_readings(tiny_cell, SEED, matmul="fp8"), ref)
    assert any(low[k] > limit for k, limit in tiny_cell.limits.items()), low


def test_compare_measures_norm_gaps_by_leaf():
    ref = {"loss": [10.0, 9.0], "grad": [1.0, 2.0, 1.5],
           "change": [3.0, 4.0, 5.0], "raw_grad": [0.5, 0.6, 1e-6]}
    prog = {"loss": [10.0, 9.5], "grad": [1.1, 2.0, 0.5],
            "change": [3.0, 4.4, 5.0]}
    got = harness.compare(prog, ref)
    assert got["loss_gap"] == pytest.approx(0.5)
    # the third leaf's reference loss gradient is nought: it is left out;
    # a leaf is measured against the larger of its own and the median norm
    assert got["grad_gap"] == pytest.approx(0.1 / 1.5)
    assert got["grad_gap_median"] == pytest.approx(np.median([0.1 / 1.5,
                                                              0.0]))
    assert got["change_gap"] == pytest.approx(0.4 / 4.0)
    assert got["change_gap_median"] == pytest.approx(np.median([0.0, 0.1]))
