"""The benchmark's manifest: ``BENCHMARK.json`` at the root of the
checkout, and the files each of its names resolves to.

- a configuration ``<config>``: ``configs/<config>.json`` (sizes, the
  program's registry name, the generator of its inputs, the solver
  settings it states, the chips it needs);
- a traffic mix ``<traffic>``: ``traffic/<traffic>.json`` (the driver
  of the window, the solver settings it adds, how the window opens,
  what the check compares and the limits of each compared number);
- the generator a configuration names in ``inputs``:
  ``generators/<inputs>.py``, whose ``make(config, key)`` returns the
  raw inputs;
- the driver a traffic mix names in ``driver``: ``drivers/<driver>.py``,
  whose ``drive(run)`` drives the program through the measured window
  and reports the end-to-end values it measures (see ``run.py``);
- a per-layer metric ``<metric>``: ``metrics/<metric>.py``, whose
  ``read(reading)`` returns the number or None;
- the correctness reference of a registry name ``<problem>``:
  ``reference/<problem>.py``;
- the work of a kernel family ``<family>``: ``kernels/<family>.py``.

A new cell, configuration, traffic mix or metric is a set of new files
and ``BENCHMARK.json`` entries; no file here names one.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import List

HERE = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def sizes(self) -> dict:
        return self.config["sizes"]

    @property
    def records(self) -> int:
        return int(self.sizes[self.config["records"]])

    @property
    def local_records(self) -> int:
        return self.records // self.chips

    @property
    def solver(self) -> dict:
        return {**self.config["solver"], **self.traffic["solver"]}

    @property
    def problem_args(self) -> dict:
        return self.config.get("problem_args", {})


_LOADED = {}


def load_module(path: Path, name: str):
    """Import the Python file ``path`` under the module name ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def component(kind: str, name: str):
    """``<kind>/<name>.py`` of the benchmark, imported once per process
    (so a jitted generator keeps its compiled program)."""
    if (kind, name) not in _LOADED:
        _LOADED[kind, name] = load_module(
            HERE / kind / f"{name}.py",
            f"chipbench_{kind}_" + name.replace(".", "_"))
    return _LOADED[kind, name]


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, workload: str, records: int = 0) -> Cell:
    """The cell named ``workload``; ``records`` > 0 replaces the number
    of records (stamps or samples) for a rehearsal at a small size."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    config = json.loads((HERE / "configs" / f"{w['config']}.json")
                        .read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    if records:
        config["sizes"] = dict(config["sizes"],
                               **{config["records"]: int(records)})
    e2e = [m for m in bench["end_to_end"] if reports(m, workload)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in moved and reports(m, workload)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer)


def problems(bench: dict, root: Path) -> List[str]:
    """What is wrong with the manifest: names and units outside the
    allowed characters, names that resolve to no file, a cell whose
    chips differ from its configuration's, too many four-chip cells."""
    out = []
    root = Path(root)
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[section]]
        out += [f"{section}: bad name {n!r}" for n in names
                if not NAME.match(n)]
        if len(set(names)) != len(names):
            out.append(f"{section}: duplicate names")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(m["unit"]):
            out.append(f"{m['name']}: bad unit {m['unit']!r}")
    configs = {c["name"]: c for c in bench["configs"]}
    for c in configs.values():
        f = (root / c["file"]).resolve()
        if not f.is_file() or f.parent != HERE / "configs" \
                or f.stem != c["name"]:
            out.append(f"config {c['name']}: no file {c['file']}")
        elif json.loads(f.read_text())["reduced"] != c["reduced"]:
            out.append(f"config {c['name']}: 'reduced' differs from its file")
    for w in bench["workloads"]:
        for key in ("config", "traffic"):
            if not NAME.match(w[key]):
                out.append(f"{w['name']}: bad {key} {w[key]!r}")
        if w["config"] not in configs:
            out.append(f"{w['name']}: unknown config {w['config']!r}")
            continue
        tfile = HERE / "traffic" / f"{w['traffic']}.json"
        if not tfile.is_file():
            out.append(f"{w['name']}: no traffic file {w['traffic']}")
            continue
        driver = json.loads(tfile.read_text())["driver"]
        if not (HERE / "drivers" / f"{driver}.py").is_file():
            out.append(f"{w['name']}: no driver {driver}")
        conf = json.loads((root / configs[w["config"]]["file"]).read_text())
        if not (HERE / "generators" / f"{conf['inputs']}.py").is_file():
            out.append(f"{w['name']}: no generator {conf['inputs']}")
        if conf["chips"] != w["chips"]:
            out.append(f"{w['name']}: chips {w['chips']} but its config "
                       f"states {conf['chips']}")
        if not (HERE / "reference" / f"{conf['problem']}.py").is_file():
            out.append(f"{w['name']}: no reference for {conf['problem']}")
    for m in bench["per_layer"]:
        if not (HERE / "metrics" / f"{m['name']}.py").is_file():
            out.append(f"metric {m['name']}: no reader")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    if four > max(1, len(bench["workloads"]) // 2):
        out.append(f"{four} four-chip cells of {len(bench['workloads'])}")
    return out
