"""A benchmark cell, found by name: its entry in ``BENCHMARK.json``, its
model configuration (``configs/<config>.json``) and the architecture
module that configuration names (``arch/<architecture>.py``), its
training job (``traffic/<traffic>.json``), the limits of its correctness
check (``limits/<cell>.json``) and the metrics it reports."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file (model fields, source)
    arch: types.ModuleType  # its architecture: weights, loss, FLOP count
    job: dict               # the traffic file: the training job
    limits: Dict[str, float]
    end_to_end: List[dict]  # metric entries of BENCHMARK.json it reports
    per_layer: List[dict]

    @property
    def model(self) -> dict:
        return self.config["model"]


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


#: what the reference and the counts take from an architecture module
ARCH_FUNCTIONS = ("init_params", "make_loss", "train_flops_per_token")


def load_arch(name, root: str = ROOT) -> types.ModuleType:
    """The architecture module ``chipbench/arch/<name>.py`` under
    ``root``; an unknown name is an error that lists the known ones."""
    here = os.path.join(root, "chipbench", "arch")
    known = sorted(f[:-3] for f in os.listdir(here) if f.endswith(".py"))
    if name not in known:
        raise KeyError(f"no architecture {name!r} in chipbench/arch; "
                       f"known: {known}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_arch_" + name.replace("-", "_").replace(".", "_"),
        os.path.join(here, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in ARCH_FUNCTIONS if not callable(getattr(mod, f,
                                                                 None))]
    if missing:
        raise TypeError(f"architecture {name!r} lacks {missing}")
    return mod


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Everything the harness needs for cell ``name``; a name that
    ``BENCHMARK.json`` does not list is an error."""
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    here = os.path.join(root, "chipbench")
    # a metric with a `workloads` key is reported in the cells it lists;
    # a per-layer metric without one, in every cell reporting what it moves
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and name in m.get("workloads", [name])]
    config = _read(os.path.join(root, configs[w["config"]]["file"]))
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        arch=load_arch(config.get("architecture"), root),
        job=_read(os.path.join(here, "traffic", w["traffic"] + ".json")),
        limits=_read(os.path.join(here, "limits", name + ".json")),
        end_to_end=end_to_end, per_layer=per_layer)
