"""``BENCHMARK.json`` and the files it names.

A cell names a configuration and a traffic mix; each is one data file,
``configs/<config>.json`` and ``traffic/<mix>.json``. A configuration names
its architecture family, which selects ``reference/<family>.py`` (weights and
the plain float32 reference) and ``cost/<family>.py`` (operations and bytes
the algorithm needs). Every metric is read by ``metrics/<metric>.py``.
Adding a cell, a configuration, a mix or a metric is adding files and
entries; no file here names one.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where the files named by ``BENCHMARK.json`` live. Tests point
    ``configs`` and ``traffic`` at their own small files."""
    configs: Path = BENCH_DIR / "configs"
    traffic: Path = BENCH_DIR / "traffic"
    metrics: Path = BENCH_DIR / "metrics"
    reference: Path = BENCH_DIR / "reference"
    cost: Path = BENCH_DIR / "cost"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    chips: int
    end_to_end: tuple       # BENCHMARK.json entries this cell reports
    per_layer: tuple


def load_benchmark(path: Path = CHECKOUT / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(directory: Path, name: str) -> dict:
    if not NAME_RE.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    with open(directory / f"{name}.json") as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(directory: Path, name: str) -> ModuleType:
    """Import ``<directory>/<name>.py`` by path (names may hold ``-``
    and ``.``, which an import statement cannot), once per process."""
    if not NAME_RE.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = re.sub(r"[^A-Za-z0-9_]", "_",
                      f"chipbench_{directory.name}_{name}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reports(metric: dict, cell: str) -> bool:
    """A metric with ``workloads`` is reported in those cells; one
    without, in every cell."""
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, cell_name: str, layout: Layout = Layout()) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[cell_name]
    e2e = tuple(m for m in bench["end_to_end"] if reports(m, cell_name))
    names = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"]
                  if reports(m, cell_name) and m["moves"] in names)
    return Cell(name=cell_name,
                config=_load_json(layout.configs, w["config"]),
                traffic=_load_json(layout.traffic, w["traffic"]),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=layer)


def family_modules(config: dict, layout: Layout = Layout()):
    """(reference, cost) modules of the configuration's family."""
    fam = config["family"]
    return load_module(layout.reference, fam), load_module(layout.cost, fam)


def problems(bench: dict, layout: Layout = Layout()) -> list:
    """What in ``bench`` breaks the naming rules or names a file that is
    not there; empty when every cell resolves."""
    out = []

    def name_ok(kind, n):
        if not isinstance(n, str) or not NAME_RE.match(n):
            out.append(f"{kind} name {n!r}")

    for c in bench["configs"]:
        name_ok("config", c["name"])
        for k in c["reduced"]:
            name_ok("reduced key", k)
        if not (CHECKOUT / c["file"]).is_file():
            out.append(f"config file {c['file']} missing")
    for w in bench["workloads"]:
        name_ok("workload", w["name"])
        name_ok("traffic", w["traffic"])
        if not (layout.traffic / f"{w['traffic']}.json").is_file():
            out.append(f"traffic file for {w['traffic']} missing")
        if not (layout.configs / f"{w['config']}.json").is_file():
            out.append(f"config file for {w['config']} missing")
    for m in bench["end_to_end"] + bench["per_layer"]:
        name_ok("metric", m["name"])
        if not UNIT_RE.match(m["unit"]):
            out.append(f"unit {m['unit']!r} of {m['name']}")
        if m["source"] not in SOURCES:
            out.append(f"source {m['source']!r} of {m['name']}")
        if not (layout.metrics / f"{m['name']}.py").is_file():
            out.append(f"reader metrics/{m['name']}.py missing")
    return out


def config_entry(bench: dict, name: str) -> Optional[dict]:
    return next((c for c in bench["configs"] if c["name"] == name), None)
