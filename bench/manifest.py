"""The manifest (``BENCHMARK.json``) and the files it names, found by name.

Everything that belongs to one configuration, traffic mix, straggler mix or
per-layer metric sits in a file of its own, so a cell is added by adding
files and a ``workloads`` entry, never by editing one that is there:

* a configuration is the JSON file its manifest entry names (``file``);
* a traffic mix is ``bench/traffic/<traffic>.json``;
* a straggler mix is ``bench/mixes/<slowdown>.json``, named by the traffic;
* a per-layer metric is read by ``bench/layers/<metric>.py``, whose
  ``read(run)`` returns a number or ``None`` when it finds nothing to read;
* a kind of matrix, named by a configuration's ``matrix``, is made by
  ``bench/matrices/<matrix>.py`` (``build(config, seed)``);
* a kind of job, named by a traffic mix's ``job``, is made and checked by
  the ``Jobs`` class of ``bench/jobs/<job>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = "bench"


def load_manifest(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    """One workload of the manifest, with every file it names loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path

    def reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        return layer_reader(metric, self.root)

    def matrix_builder(self) -> Callable[..., Any]:
        return find_module("matrices", self.config["matrix"], self.root).build

    def jobs_class(self) -> type:
        return find_module("jobs", self.traffic["job"], self.root).Jobs


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / BENCH / "traffic" / f"{w['traffic']}.json")
    mix = _load_json(root / BENCH / "mixes" / f"{traffic['slowdown']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, mix=mix,
                end_to_end=[m for m in man["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in man["per_layer"] if _applies(m, name)],
                root=root)


def find_module(kind: str, name: str, root: pathlib.Path = ROOT):
    """The module ``bench/<kind>/<name>.py``, loaded from its file."""
    path = root / BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str, root: pathlib.Path = ROOT
                 ) -> Callable[[Any], Optional[float]]:
    """The ``read`` function of ``bench/layers/<metric>.py``."""
    return find_module("layers", metric, root).read
