"""BENCHMARK.json resolves: every cell to its configuration, traffic,
driver, generator and reference, every per-layer metric to its reader,
with names and units in the allowed characters."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import cells  # noqa: E402

BENCH = cells.load_benchmark(ROOT)


def test_manifest_has_no_problems():
    assert cells.problems(BENCH, ROOT) == []


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_resolves_and_reports(name):
    cell = cells.resolve(BENCH, name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    assert cell.records % cell.chips == 0
    assert set(cell.traffic["limits"]) and cell.traffic["window"][
        "open_after_chunks"] >= 1


def _components():
    out = []
    for name in [w["name"] for w in BENCH["workloads"]]:
        cell = cells.resolve(BENCH, name)
        out += [("drivers", cell.traffic["driver"], "drive"),
                ("generators", cell.config["inputs"], "make"),
                ("reference", cell.config["problem"], "check")]
    out += [("metrics", m["name"], "read") for m in BENCH["per_layer"]]
    return sorted(set(out))


@pytest.mark.parametrize("kind,name,entry", _components())
def test_each_name_resolves_to_its_component(kind, name, entry):
    assert callable(getattr(cells.component(kind, name), entry))


def test_a_missing_driver_or_generator_is_reported(monkeypatch):
    real = json.loads
    broken = {"driver": "no_such_driver", "inputs": "no_such_generator"}

    def loads(text, *a, **k):
        d = real(text, *a, **k)
        return {**d, **{k2: v for k2, v in broken.items() if k2 in d}} \
            if isinstance(d, dict) else d

    monkeypatch.setattr(cells.json, "loads", loads)
    found = cells.problems(BENCH, ROOT)
    assert any("no driver no_such_driver" in p for p in found)
    assert any("no generator no_such_generator" in p for p in found)


def test_names_and_units_are_checked():
    bad = json.loads(json.dumps(BENCH))
    bad["per_layer"][0]["name"] = "bad name"
    bad["end_to_end"][0]["unit"] = "tokens per second"
    found = cells.problems(bad, ROOT)
    assert any("bad name" in p for p in found)
    assert any("bad unit" in p for p in found)


def test_at_most_half_the_cells_take_four_chips():
    bad = json.loads(json.dumps(BENCH))
    for w in bad["workloads"]:
        w["chips"] = 4
    assert any("four-chip" in p for p in cells.problems(bad, ROOT))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        cells.resolve(BENCH, "no_such.cell")
