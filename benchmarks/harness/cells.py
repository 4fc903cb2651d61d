"""Finding a cell's files by the names in ``BENCHMARK.json``. Nothing here
names a cell, a model or a metric: a later PR adds a configuration
(``configs/<config>.json``), a traffic mix (``traffic/<traffic>.json``), a
cell's own parameters (``cells/<cell>.json``, optional) or a metric reader
(``metrics/<metric>.py``) as files and entries, and edits nothing.
"""

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[2]


class CellError(ValueError):
    """``BENCHMARK.json`` or one of the files it names does not fit."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]       # the configuration file, as run
    traffic: Dict[str, Any]      # the traffic file with the cell's parameters laid over it
    end_to_end: List[dict]       # the metrics this cell reports, by entry
    per_layer: List[dict]
    root: pathlib.Path


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _find(root: pathlib.Path, bench: dict, relative: str) -> Optional[pathlib.Path]:
    """``relative`` under the first of the benchmark's ``paths`` that has it."""
    for base in bench["paths"]:
        candidate = root / base / relative
        if candidate.is_file():
            return candidate
    return None


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def find_cell(bench: dict, name: str, root: pathlib.Path = ROOT) -> Cell:
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; it has "
                        f"{[w['name'] for w in bench['workloads']]}")
    entry = entries[0]
    configs = [c for c in bench["configs"] if c["name"] == entry["config"]]
    if not configs:
        raise CellError(f"workload {name!r} names configuration "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    with open(root / configs[0]["file"]) as f:
        config = json.load(f)
    traffic_file = _find(root, bench, f"traffic/{entry['traffic']}.json")
    if traffic_file is None:
        raise CellError(f"no traffic/{entry['traffic']}.json under "
                        f"{bench['paths']}")
    with open(traffic_file) as f:
        traffic = json.load(f)
    own = _find(root, bench, f"cells/{name}.json")
    if own is not None:
        with open(own) as f:
            traffic.update(json.load(f).get("traffic", {}))
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                root=root)


def load_module(root: pathlib.Path, bench: dict, folder: str, name: str):
    """The module ``<folder>/<name>.py`` under the benchmark's paths, loaded
    by file (a metric's name may hold dots), or None where there is none."""
    path = _find(root, bench, f"{folder}/{name}.py")
    if path is None:
        return None
    spec = importlib.util.spec_from_file_location(
        f"_benchmark_{folder}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
