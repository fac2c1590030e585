"""FLOPs per token, parameter counts and commit bytes against hand
counts for the benchmark's configurations, and the peaks table's refusal
of an unknown chip."""
import json
import os

import jax
import pytest

from chipbench import flops
from chipbench.cells import HERE, load_arch

#: rows R of each configuration's packed commit buffer
ROWS = {"tinygpt15m": 125_128, "gpt2-124m": 965_976}


def _config(name):
    """A configuration's architecture module and its model."""
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    return load_arch(cfg["architecture"]), cfg["model"]


def test_tinygpt15m_counts():
    arch, m = _config("tinygpt15m")
    # per layer: q,k,v,o 4 x 256^2 and the MLP 2 x 256 x 1024; tied head
    mm = 4 * (4 * 256 * 256 + 2 * 256 * 1024) + 50257 * 256
    assert arch.matmul_params(m) == mm == 16_011_520
    # + LayerNorm scales and biases: 2 per layer x 2 x 256, final 2 x 256
    assert flops.n_params(arch, m) == mm + 4 * 4 * 256 + 2 * 256 \
        == 16_016_128
    per_token = 6 * mm + 12 * 4 * 8 * 32 * 512
    assert arch.train_flops_per_token(m, 512) == per_token
    assert per_token / 1e9 == pytest.approx(0.1024, abs=1e-3)


def test_gpt2_124m_counts():
    arch, m = _config("gpt2-124m")
    layer = 4 * 768 * 768 + 2 * 768 * 3072
    mm = 12 * layer + 50257 * 768
    assert arch.matmul_params(m) == mm
    # LayerNorms (2 x 2 x 768 per layer, 2 x 768 final), q/k/v biases
    # (3 x 768) and MLP biases (3072 + 768)
    extra = 12 * (4 * 768 + 3 * 768 + 3072 + 768) + 2 * 768
    assert flops.n_params(arch, m) == mm + extra == 123_644_160
    per_token = 6 * mm + 12 * 12 * 12 * 64 * 512
    assert arch.train_flops_per_token(m, 512) == per_token
    assert per_token / 1e9 == pytest.approx(0.798, abs=1e-3)


@pytest.mark.parametrize("name", ["tinygpt15m", "gpt2-124m"])
def test_packed_rows_match_the_programs_layout(name):
    from repro.configs.base import ModelConfig
    from repro.core import packing
    from repro.models import build_model
    arch, m = _config(name)
    model = build_model(ModelConfig(**m))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sorted(x.size for x in jax.tree.leaves(shapes)) == sorted(
        flops.leaf_sizes(arch, m))
    assert flops.packed_rows(arch, m) == packing.build_layout(
        shapes).n_rows == ROWS[name]


def test_commit_bytes():
    rows = 125_128                        # tinygpt-15m's packed buffer
    row = 128 * 4
    assert flops.commit_bytes(rows, 1) == 7 * rows * row
    assert flops.commit_bytes(rows, 4) == (5 + 8) * rows * row
    assert flops.commit_bytes(rows, 1) / 1e6 == pytest.approx(448.5, abs=0.1)


def test_peaks_refuse_an_unknown_chip():
    from chipbench.harness import load_peaks
    v5e = load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        load_peaks("TPU v9")
    with pytest.raises(KeyError):
        load_peaks("cpu")
