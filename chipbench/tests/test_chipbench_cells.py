"""Cells, configurations, jobs and per-layer metrics are files found by
name; the command refuses a machine without the cell's chips."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.cells import HERE, ROOT, load_cell

BENCH = os.path.join(ROOT, "BENCHMARK.json")


def _copy_benchmark(tmp_path):
    shutil.copy(BENCH, tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def test_every_cell_of_the_benchmark_loads():
    with open(BENCH) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.job["seq_len"] > 0 and cell.model["d_model"] > 0
        assert set(cell.limits) <= set(harness.compare(
            {"loss": [0.0], "grad": [1.0], "change": [1.0]},
            {"loss": [0.0], "grad": [1.0], "change": [1.0],
             "raw_grad": [1.0]}))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(harness._metric_reader(m["name"]))


def test_a_new_cell_is_found_by_name_without_editing_any_file(tmp_path):
    root = _copy_benchmark(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    # a throwaway configuration, job, limits and per-layer metric
    cfg = json.loads((root / "chipbench/configs/tinygpt15m.json").read_text())
    cfg["model"]["n_layers"] = 2
    (root / "chipbench/configs/scratch-model.json").write_text(json.dumps(cfg))
    job = json.loads((root / "chipbench/traffic/paper-k1.json").read_text())
    job["n_workers"] = 2
    (root / "chipbench/traffic/scratch-job.json").write_text(json.dumps(job))
    (root / "chipbench/limits/scratch-model.scratch-job.json").write_text(
        json.dumps({"loss_gap": 0.5}))
    (root / "chipbench/metrics/scratch_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "scratch-model", "source": "x",
                             "file": "chipbench/configs/scratch-model.json",
                             "reduced": ["n_layers"], "why": "x"})
    bench["workloads"].append({"name": "scratch-model.scratch-job",
                               "config": "scratch-model",
                               "traffic": "scratch-job", "chips": 1,
                               "why": "x"})
    # the new cell reports tokens_per_s
    tps = next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")
    tps["workloads"].append("scratch-model.scratch-job")
    bench["per_layer"].append({"name": "scratch_metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "x", "moves": "tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell("scratch-model.scratch-job", root=str(root))
    assert cell.model["n_layers"] == 2 and cell.job["n_workers"] == 2
    assert cell.limits == {"loss_gap": 0.5}
    # a per-layer metric is reported in every cell that reports what it
    # moves, and in no other
    assert "scratch_metric" in [m["name"] for m in cell.per_layer]
    assert "tokens_per_s" in [m["name"] for m in cell.end_to_end]
    other = load_cell("tinygpt15m.paper-k1", root=str(root))
    assert "scratch_metric" in [m["name"] for m in other.per_layer]
    int8 = load_cell("tinygpt15m.w16-k4-int8", root=str(root))
    assert "scratch_metric" not in [m["name"] for m in int8.per_layer]
    spec_path = root / "chipbench/metrics/scratch_metric.py"
    import importlib.util
    spec = importlib.util.spec_from_file_location("scratch_metric", spec_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read(None) == 42.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_a_quantity_split_by_cells_shares_its_reader():
    """`tokens_per_s.int8` is tokens_per_s in the cells that report it
    under that name, and `compress_ms.int8` is read by compress_ms's
    reader."""
    cell = load_cell("tinygpt15m.w16-k4-int8")
    assert [m["name"] for m in cell.end_to_end if "tokens" in m["name"]] \
        == ["tokens_per_s.int8"]
    assert all(m["name"].endswith(".int8") for m in cell.per_layer)
    assert harness.quantity("tokens_per_s.int8") == "tokens_per_s"

    class Run:
        spans = [("compress_roundtrip", 0.0, 0.25, 1),
                 ("compress_roundtrip", 1.0, 1.5, 1)]

        def span_ms(self, name):
            return [(b - a) * 1e3 for n, a, b, _ in self.spans if n == name]
    assert harness._metric_reader("compress_ms.int8")(Run()) == \
        pytest.approx(375.0)


def test_a_traffic_file_sets_any_field_of_the_run(tmp_path):
    """A new mix is a new traffic file: every key but the harness's own
    goes to the program's RunConfig, and the reference refuses a field it
    does not follow."""
    root = _copy_benchmark(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    job = json.loads((root / "chipbench/traffic/paper-k1.json").read_text())
    job.update(mixture_alpha=0.1, ckpt_every=5, non_iid=True)
    (root / "chipbench/traffic/scratch-mix.json").write_text(json.dumps(job))
    (root / "chipbench/limits/tinygpt15m.scratch-mix.json").write_text(
        json.dumps({"loss_gap": 0.5}))
    bench["workloads"].append({"name": "tinygpt15m.scratch-mix",
                               "config": "tinygpt15m",
                               "traffic": "scratch-mix", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = load_cell("tinygpt15m.scratch-mix", root=str(root))
    rc = harness.run_config(cell, seed=7)
    assert (rc.mixture_alpha, rc.ckpt_every, rc.non_iid, rc.seed) == (
        0.1, 5, True, 7)
    assert rc.worker_paces == (1.0, 2.0, 6.0, 15.0)
    assert rc.outer.heloco.c_ok == job["outer"]["heloco"]["c_ok"]
    with pytest.raises(ValueError, match="mixture_alpha"):
        harness.reference_readings(cell, 7)
    job["not_a_field"] = 1
    (root / "chipbench/traffic/scratch-mix.json").write_text(json.dumps(job))
    with pytest.raises(TypeError, match="not_a_field"):
        harness.run_config(load_cell("tinygpt15m.scratch-mix",
                                     root=str(root)), seed=7)


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no-such-cell"):
        load_cell("no-such-cell")


def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "tinygpt15m.paper-k1", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_command_refuses_a_machine_without_a_tpu():
    res = _command(ROOT)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert '"metrics"' not in res.stdout and '"correct"' not in res.stdout
