"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix; everything that belongs to one of them sits in a file of its own:

* ``configs[i].file`` — the configuration: its layers as they are run,
  where the mapping comes from, the precision the served programs
  compute in, and the limit of the output check.  Its layers may each
  carry ``"glue"``, the fields of the program's ``GlueSpec`` for that
  layer (every layer or none; without it the program infers a chain or
  a DenseNet concat from the channel counts);
* the configuration's ``"reference"`` — its plain reference, a module
  under ``bench/`` named relative to the repository root, with
  ``make_data``, ``forward_blocks`` and ``useful_flops_per_image``;
  without the key, ``bench/reference.py``;
* ``bench/traffic/<traffic>.json`` — the traffic mix's parameters;
* ``bench/cells/<workload>.json`` — what is fixed for this cell alone
  (its offered rate);
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric; a
  metric split by the cells it moves (``batch_fill.poisson``,
  ``batch_fill.backlog``) may share the reader of its stem
  (``batch_fill.py``).

Adding a cell, a configuration, a mix or a metric therefore means adding
files and ``BENCHMARK.json`` entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    fixed: dict                     # bench/cells/<name>.json
    end_to_end: Tuple[dict, ...]    # the entries this cell reports
    per_layer: Tuple[dict, ...]

    @property
    def rate_per_s(self) -> float:
        return float(self.fixed["rate_per_s"])


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(root / configs[w["config"]]["file"]),
        traffic=_json(bench / "traffic" / f"{w['traffic']}.json"),
        fixed=_json(bench / "cells" / f"{name}.json"),
        end_to_end=tuple(m for m in spec["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _reports(m, name)))


def load_config(name: str, root: Path = ROOT) -> dict:
    """A configuration's file by its name in ``configs``."""
    configs = {c["name"]: c for c in load_spec(root)["configs"]}
    return _json(root / configs[name]["file"])


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``,
    else from the reader of the name's stem (up to its first dot)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        path = path.with_name(name.split(".")[0] + ".py")
    return _load(path, "bench_metric_" + name).read


def reference_module(config: dict, root: Path = ROOT):
    """The configuration's plain reference: the module its
    ``"reference"`` names, relative to ``root`` and under its ``bench/``,
    else ``bench/reference.py``."""
    rel = config.get("reference", "bench/reference.py")
    path = (root / rel).resolve()
    if not path.is_relative_to((root / "bench").resolve()):
        raise ValueError(f"{config.get('name')}: reference {rel!r} is not "
                         f"a module under bench/")
    return _load(path, "bench_reference_" + path.stem)


def _load(path: Path, name: str):
    """The module in the file ``path``, run as ``name`` (dots and dashes
    made underscores)."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
