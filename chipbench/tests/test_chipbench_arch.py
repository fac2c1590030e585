"""A configuration brings its own architecture: the reference's weights
and loss and the counts come from the module its file names, a new one
is found by name without editing any file, and ``run_config`` builds the
program's nested configuration groups from a configuration file."""
import json
import os
import shutil

import jax
import pytest

from chipbench import flops, harness
from chipbench.cells import HERE, ROOT, load_arch, load_cell
from chipbench.reference import Reference
from test_chipbench_check import _tiny

READINGS = os.path.join(os.path.dirname(__file__), "reference_readings.json")
VARIANTS = {"bf16": {}, "fp8": {"matmul": "fp8"},
            "half_batch": {"fault": "half_batch"},
            "state_unchanged": {"fault": "state_unchanged"},
            "delta_altered": {"fault": "delta_altered"}}


def _copy_benchmark(root):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def _add_cell(root, config: dict, traffic: str = "paper-k1") -> str:
    """A configuration file and a cell of it on ``traffic``, with a
    limits file: entries and files only."""
    name = config["name"]
    (root / "chipbench/configs" / (name + ".json")).write_text(
        json.dumps(config))
    cell = f"{name}.{traffic}"
    (root / "chipbench/limits" / (cell + ".json")).write_text(
        json.dumps({"loss_gap": 0.5}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "x",
                             "file": f"chipbench/configs/{name}.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": traffic, "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


@pytest.fixture(scope="module")
def tiny_cells(tmp_path_factory):
    return {name: _tiny(tmp_path_factory, name)
            for name in ("tinygpt15m.paper-k1", "tinygpt15m.w16-k4-int8")}


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("name", ["tinygpt15m.paper-k1",
                                  "tinygpt15m.w16-k4-int8"])
def test_the_dense_reference_reads_as_before(tiny_cells, name, variant):
    """Through the configuration's architecture module the reference
    reads, bit for bit, what it read when the dense block was its own:
    the sound reference, the fp8 control and each planted fault."""
    with open(READINGS) as f:
        recorded = json.load(f)
    cell = tiny_cells[name]
    assert cell.arch.__file__.endswith(os.path.join("arch", "dense.py"))
    got = harness.reference_readings(cell, recorded["seed"],
                                     **VARIANTS[variant])
    want = recorded["readings"][name][variant]
    assert set(got) == set(want)
    for key, value in want.items():
        read = list(got[key]) if key == "names" else [float(x) for x in
                                                      got[key]]
        assert read == value, key


#: appended to a copy of dense.py: the same block under another name,
#: with one leaf more, a loss 1 higher and one FLOP more a token, so that
#: whatever reaches the reference and the counts shows where it came from
ALIAS = '''

_dense_init, _dense_loss, _dense_flops = (init_params, make_loss,
                                          train_flops_per_token)


def init_params(cfg, seed):
    params = _dense_init(cfg, seed)
    params["scratch"] = {"w": jnp.zeros((7,))}
    return params


def make_loss(cfg, matmul="bf16", half_batch=False):
    loss = _dense_loss(cfg, matmul, half_batch)
    return lambda params, tokens, labels: loss(params, tokens, labels) + 1.0


def train_flops_per_token(cfg, seq):
    return _dense_flops(cfg, seq) + 1.0
'''


def test_a_new_architecture_needs_no_edit(tmp_path):
    """A configuration file naming a module added to ``chipbench/arch/``
    and a cell of it: ``load_cell``, the reference and the counts use
    that module, and no file already there is edited."""
    root = _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    arch_dir = root / "chipbench/arch"
    (arch_dir / "scratch_arch.py").write_text(
        (arch_dir / "dense.py").read_text() + ALIAS)
    cfg = json.loads((root / "chipbench/configs/tinygpt15m.json").read_text())
    cfg.update(name="scratch-model", architecture="scratch_arch")
    cfg["model"].update(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                        head_dim=16, d_ff=64, vocab_size=128)
    job = json.loads((root / "chipbench/traffic/paper-k1.json").read_text())
    job.update(batch_size=2, seq_len=8, eval_batch=2)
    (root / "chipbench/traffic/scratch-job.json").write_text(json.dumps(job))
    name = _add_cell(root, cfg, "scratch-job")

    cell = load_cell(name, root=str(root))
    dense = load_arch("dense", root=str(root))
    assert cell.arch.__file__ == str(arch_dir / "scratch_arch.py")
    m = cell.model
    assert flops.leaf_sizes(cell.arch, m) == flops.leaf_sizes(dense, m) + [7]
    assert flops.n_params(cell.arch, m) == flops.n_params(dense, m) + 7
    rows = sum(-(-n // 128) for n in flops.leaf_sizes(dense, m)) + 1
    assert flops.packed_rows(cell.arch, m) == -(-rows // 8) * 8
    assert cell.arch.train_flops_per_token(m, 8) == \
        dense.train_flops_per_token(m, 8) + 1.0

    class Run:          # what the per-layer readers see of a traced run
        tokens, window_s, n_chips = 1000, 2.0, 1
        peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

        def kernel_seconds(self, role):
            return 0.5, 3

        def arrivals(self):
            return [1, 1, 4]
    Run.cell = cell
    assert harness._metric_reader("mfu")(Run()) == pytest.approx(
        100.0 * 1000 * (dense.train_flops_per_token(m, 8) + 1.0) / 2e12)
    need = sum(flops.commit_bytes(flops.packed_rows(cell.arch, m), k)
               for k in (1, 1, 4))
    assert harness._metric_reader("commit_roofline")(Run()) == \
        pytest.approx(100.0 * need / (0.5 * 1e11))

    got = Reference(cell.arch, m, cell.job, 11).run(1)
    base = Reference(dense, m, cell.job, 11).run(1)
    assert got["names"] == base["names"] + ["['scratch']['w']"]
    assert got["loss"][0] == pytest.approx(base["loss"][0] + 1.0, abs=1e-5)
    assert list(got["raw_grad"][:-1]) == pytest.approx(
        list(base["raw_grad"]), rel=1e-5)
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_an_unknown_architecture_is_an_error(tmp_path):
    root = _copy_benchmark(tmp_path)
    path = root / "chipbench/configs/tinygpt15m.json"
    cfg = json.loads(path.read_text())
    cfg["architecture"] = "no-such-arch"
    path.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match=r"no-such-arch.*known: \['dense'\]"):
        load_cell("tinygpt15m.paper-k1", root=str(root))
    del cfg["architecture"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match="known"):
        load_cell("tinygpt15m.paper-k1", root=str(root))


def test_run_config_builds_nested_groups(tmp_path):
    """A model with a nested ``moe`` group and JSON lists, at the top
    level and inside a group, becomes a ``ModelConfig`` of configuration
    classes and tuples that hashes, and the program builds it."""
    from repro.configs.base import MoEConfig, XLSTMConfig
    from repro.models import build_model
    root = _copy_benchmark(tmp_path)
    cfg = {"name": "scratch-moe", "architecture": "dense", "model": {
        "name": "scratch-moe", "family": "moe", "block_kind": "moe",
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "d_ff": 128, "vocab_size": 128, "head_dim": 16,
        "moe": {"n_experts": 4, "top_k": 2, "expert_d_ff": 64,
                "group_size": 64},
        "xlstm": {"slstm_at": [1]}, "act_batch_axes": ["data"],
        "compute_dtype": "float32", "remat": False, "scan_layers": False}}
    cell = load_cell(_add_cell(root, cfg), root=str(root))
    model = harness.run_config(cell, seed=3).model
    assert isinstance(model.moe, MoEConfig) and model.moe.top_k == 2
    assert isinstance(model.xlstm, XLSTMConfig)
    assert model.xlstm.slstm_at == (1,) and model.act_batch_axes == ("data",)
    hash(model)
    params = build_model(model).init(jax.random.PRNGKey(0))
    assert params["blocks_list"]["layer_00"]["moe"]["w_gate"].shape == (
        4, 64, 64)
