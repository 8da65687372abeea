"""Loaders: the cell, its deployment, its traffic mix and its metric readers,
each found by the name ``BENCHMARK.json`` gives it.

A later cell adds files and entries; nothing here names a configuration, a
mix or a metric.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
BENCHMARK_JSON = CHECKOUT / "BENCHMARK.json"


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found."""


def load_benchmark(path: pathlib.Path = BENCHMARK_JSON) -> Dict:
    if not path.is_file():
        raise SpecError(f"no benchmark definition at {path}")
    return json.loads(path.read_text())


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"unknown {what} {name!r}; known: "
                    f"{sorted(e['name'] for e in entries)}")


def _data_file(root: pathlib.Path, sub: str, name: str, what: str) -> Dict:
    path = root / sub / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"unknown {what} {name!r}: no file {path}")
    data = json.loads(path.read_text())
    if data.get("name") != name:
        raise SpecError(f"{path} names itself {data.get('name')!r}, "
                        f"not {name!r}")
    return data


def load_config(name: str, root: pathlib.Path = HERE) -> Dict:
    """configs/<name>.json: one deployment."""
    return _data_file(root, "configs", name, "configuration")


def load_traffic(name: str, root: pathlib.Path = HERE) -> Dict:
    """traffic/<name>.json: one traffic mix, read by drive.py."""
    return _data_file(root, "traffic", name, "traffic mix")


def load_reader(name: str, root: pathlib.Path = HERE
                ) -> Callable[[Dict], Optional[float]]:
    """metrics/<name>.py's ``read(record)``: the metric's value, or None
    where the record holds nothing to read."""
    path = root / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"unknown metric {name!r}: no reader {path}")
    mod_spec = importlib.util.spec_from_file_location(
        "readout_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(record)")
    return mod.read


def cell_metrics(bench: Dict, cell: str, section: str) -> List[Dict]:
    """The entries of ``section`` ("end_to_end" or "per_layer") that the
    cell reports: those that list it, and those that list no cells."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def resolve_cell(bench: Dict, cell: str, root: pathlib.Path = HERE) -> Dict:
    """Everything one run of ``cell`` needs, loaded by name."""
    w = _by_name(bench["workloads"], cell, "workload")
    _by_name(bench["configs"], w["config"], "configuration")
    return {
        "workload": w,
        "config": load_config(w["config"], root),
        "traffic": load_traffic(w["traffic"], root),
        "end_to_end": cell_metrics(bench, cell, "end_to_end"),
        "per_layer": cell_metrics(bench, cell, "per_layer"),
    }
