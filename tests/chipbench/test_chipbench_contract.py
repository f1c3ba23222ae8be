"""``BENCHMARK.json`` against the rules its harness and its check rely on,
and every name in it against the file the harness finds by that name."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert 1 <= len(BENCH["paths"]) <= 16
    assert 1 <= len(BENCH["configs"]) <= 24 and 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16 and 1 <= len(BENCH["per_layer"]) <= 128
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_command_stays_inside_the_paths():
    cmd = BENCH["command"]
    assert len(cmd) <= 32 and cmd[1] == "chipbench/run.py"
    for word in cmd:
        assert not word.startswith("/") and ".." not in word.split("/")
    assert (ROOT / cmd[1]).is_file()
    assert any(cmd[1].startswith(p + "/") for p in BENCH["paths"])


def test_a_full_check_fits_its_time_with_24_cells():
    s = BENCH["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_are_plain(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_each_config_file_states_its_cut(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    f = json.loads((ROOT / conf["file"]).read_text())
    assert f["name"] == conf["name"]
    assert sorted(f["reduced"]) == sorted(conf["reduced"])
    for key in conf["reduced"]:
        assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
        assert {"source", "here", "why"} <= set(f["reduced"][key])
    assert (ROOT / f["reference"]).is_file()
    assert (ROOT / "chipbench" / "systems" / f"{f['system']}.py").is_file()
    assert f["guarantees"]
    assert any(c["config"] == conf["name"] for c in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_names_files_that_exist(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    assert (ROOT / "chipbench" / "traffic" / f"{cell['traffic']}.json").is_file()
    reported = [m for m in METRICS if cell["name"] in m.get("workloads", [cell["name"]])]
    kinds = {("e2e" if m in BENCH["end_to_end"] else "layer") for m in reported}
    assert kinds == {"e2e", "layer"}
    assert any(m["name"] == "setup_s" for m in reported)


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_each_metric_has_its_reader_and_fields(metric):
    e2e = metric in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source"} | (
        {"bound", "workloads"} if e2e else {"layer", "moves", "workloads"}
    )
    assert set(metric) <= allowed
    assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert (ROOT / "chipbench" / "metrics" / f"{metric['name']}.py").is_file()
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert metric["layer"] and "\n" not in metric["layer"]
        moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
        assert moved, f"{metric['name']} moves an unknown metric"
        for cell in metric.get("workloads", []):
            assert cell in moved[0].get("workloads", CELLS)
    if "roofline" in metric["name"]:
        assert metric["unit"] == "%" and metric["name"].split(".")[0].endswith("_roofline")


def test_setup_is_reported_with_its_bound():
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25 and "workloads" not in setup[0]


def test_peaks_name_their_source():
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    assert "TPU v5" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
