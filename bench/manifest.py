"""Find a cell's files by name.

``BENCHMARK.json`` names configurations, traffic mixes and metrics; each
lives in a file of its own under ``bench/``. Nothing here lists a cell, a
metric, a kernel or a model family: adding a file and an entry in the
manifest adds it. A cell records the directory it was loaded from, and
every module of the cell is loaded from there.

A configuration file holds the published config under its own key names.
Where the model does not fit one chip whole, the file's ``cut`` holds the
values kept on this chip, in the same key names (``{"num_hidden_layers":
4}``); every key of it is named in the file's ``reduced``, and its
``assumed`` says, under the same key, what deployment the cut stands for.
"""
from __future__ import annotations

import copy
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"


def load_manifest(path: Path = MANIFEST) -> dict:
    return json.loads(Path(path).read_text())


def load_module(kind: str, name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """Import ``bench/<kind>/<name>.py`` by its path. A name may hold
    dots, so the module is loaded from its file, not by import name."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str, bench_dir: Path = BENCH_DIR) -> dict:
    path = bench_dir / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return json.loads(path.read_text())


def load_peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Published peaks of one chip, keyed by JAX's ``device_kind``. An
    unknown kind is an error: no roofline is ever taken against a guess."""
    table = json.loads((bench_dir / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]


def file_value(config: dict, key: str):
    """A configuration file's value at ``key``; a dot steps into a group
    (``model.head_dim``)."""
    node = config
    for part in key.split("."):
        if part not in node:
            raise KeyError(f"configuration {config.get('name')!r} has no {key!r}")
        node = node[part]
    return node


def apply_cut(config: dict) -> dict:
    """The configuration as this chip runs it: the file's ``cut`` over the
    published values, which stay beside it under ``published``. A file
    with no ``cut`` comes back as it is."""
    cut = config.get("cut")
    if not cut:
        return config
    name = config.get("name")
    unnamed = sorted(set(cut) - set(config.get("reduced", [])))
    if unnamed:
        raise ValueError(f"configuration {name!r} cuts {unnamed} without"
                         " naming them in its reduced")
    unknown = sorted(set(cut) - set(config))
    if unknown:
        raise ValueError(f"configuration {name!r} cuts {unknown}, which it"
                         " does not hold")
    out = copy.deepcopy(config)
    out["published"] = {k: config[k] for k in cut}
    out.update(cut)
    return out


@dataclass
class Cell:
    """One workload of the manifest with everything it names, loaded."""

    name: str
    chips: int
    config: dict  # with its cut applied (``apply_cut``)
    traffic: dict
    end_to_end: list  # manifest entries reported with --trace 0
    per_layer: list  # manifest entries reported with --trace 1
    bench_dir: Path  # where its modules are loaded from


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, manifest: dict | None = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    manifest = load_manifest() if manifest is None else manifest
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    config = json.loads((bench_dir.parent / cfg_entry["file"]).read_text())
    traffic = load_json("traffic", w["traffic"], bench_dir)
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=apply_cut(config),
        traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir,
    )
