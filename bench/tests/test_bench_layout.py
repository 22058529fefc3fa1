"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic mix, limits and metric readers by name,
and every name, unit and number keeps to the benchmark's format."""
import importlib.util
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_full_check_fits_its_time_budget():
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and _line(cfg["why"]) and _line(cfg["source"])
    assert cfg["file"].startswith("bench/configs/")
    body = json.loads((ROOT / cfg["file"]).read_text())
    assert body["name"] == cfg["name"]
    assert body["reduced"] == cfg["reduced"] == []
    hs = body["layer_sizes"]
    assert len(hs) == body["depth"] and hs[-1] == body["input_features"]
    assert any(c["config"] == cfg["name"] for c in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    assert set(cell) == CELL_KEYS
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert _line(cell["why"])
    assert cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert mix["groups"] and {g["op"] for g in mix["groups"]} <= {"step", "score"}
    assert {g["send"]["loop"] for g in mix["groups"]} <= {"closed", "open"}
    limits = json.loads((BENCH / "limits" / f"{cell['name']}.json").read_text())
    assert set(limits) == {"excess_gap_max", "excess_gap_mean"}
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = [m for m in SPEC["per_layer"] if cell["name"] in m["workloads"]]
    assert layers
    for m in layers:
        assert m["moves"] in e2e


def test_names_are_unique_and_pairs_appear_once():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(c["chips"] == 4 for c in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("m", SPEC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == E2E_KEYS
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    cells = {c["name"] for c in SPEC["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    assert "def read(ctx)" in (BENCH / "metrics" / f"{m['name']}.py").read_text()


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(m):
    assert set(m) == LAYER_KEYS | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and _line(m["layer"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"
    path = BENCH / "metrics" / f"{m['name']}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{m['name']}", path)
    assert spec is not None and path.is_file()
    text = path.read_text()
    assert "def read(ctx)" in text


def test_peaks_table_names_its_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
