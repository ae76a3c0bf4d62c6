"""Every cell of ``BENCHMARK.json`` resolves, by name, to its files, and the
file keeps the benchmark's shape."""

import importlib
import json
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs", "workloads",
                           "end_to_end", "per_layer"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_and_units(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    for e in BENCH[section]:
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    import run

    c = run.load_cell(cell)
    cfg, traffic = c.config, c.traffic
    app = importlib.import_module(f"apps.{cfg['app']}")
    assert callable(app.tile_fn) and callable(app.reference) and callable(app.viewports)
    path = importlib.import_module(f"paths.{cfg['path']}")
    assert path.Runner.spans == path.SPANS
    sizes = importlib.import_module(f"schedules.{traffic['technique']}").sizes(
        cfg["N"], cfg["P"], traffic["mode"])
    assert sum(sizes) == cfg["N"] and max(sizes) <= app.TILE
    assert cfg["chips"] == c.chips
    # every cell reports setup_s, another end-to-end metric, and a per-layer metric
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2 and c.per_layer
    for name in c.per_layer:
        assert callable(importlib.import_module(f"metrics.{name}").read)


def test_configs_state_their_cuts():
    for entry in BENCH["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert entry["file"].startswith("benchmarks/chip/configs/")
        assert entry["reduced"] == cfg["reduced"]
        for key in cfg["reduced"]:
            assert key in cfg["published"] and key in cfg["assumed"]
        # a departure forced by the chip is stated beside the source's value,
        # and is not a cut
        for key in cfg.get("departures", {}):
            assert key in cfg["published"] and key not in cfg["reduced"]


def test_per_layer_metrics_name_cells_that_exist():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert (HERE / "metrics" / f"{m['name']}.py").exists()
