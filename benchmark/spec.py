"""Finds a cell's files by the names BENCHMARK.json gives: the workload's
config in configs/<config>.json, its traffic in traffic/<traffic>.json,
the traffic's driver in drivers/<driver>.py, every metric's definition in
end_to_end/<metric>.json or layer_metrics/<metric>.json. A later PR adds
files and entries; nothing here names a cell, a config or a metric."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{path}: {e}") from e


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object           # the module drivers/<traffic.driver>.py
    end_to_end: list         # [(BENCHMARK.json entry, definition)]
    per_layer: list


def manifest(root: str) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def bench_dir(root: str, man: dict) -> str:
    return os.path.join(root, man["paths"][0])


def load_driver(bdir: str, name: str):
    path = os.path.join(bdir, "drivers", f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"no driver {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.drivers.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    man = manifest(root)
    bdir = bench_dir(root, man)
    entry = next((w for w in man["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(has {[w['name'] for w in man['workloads']]})")
    cfg_entry = next((c for c in man["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {workload!r} names config "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    config = _json(os.path.join(root, cfg_entry["file"]))
    traffic = _json(os.path.join(bdir, "traffic", f"{entry['traffic']}.json"))

    def defs(kind: str, folder: str):
        return [(m, _json(os.path.join(bdir, folder, f"{m['name']}.json")))
                for m in man[kind] if applies(m, workload)]

    return Cell(workload, entry["chips"], config, traffic,
                load_driver(bdir, traffic["driver"]),
                defs("end_to_end", "end_to_end"),
                defs("per_layer", "layer_metrics"))
