"""``BENCHMARK.json`` against the contract's limits, and every name in it
against a file the harness can find."""

import json
import pathlib
import re

import pytest

from benchmarks.harness import cells

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_SUFFIXES = ("_size", "_dim", "_rank", "_per_tok")


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(BENCH["command"]) <= 32
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all((REPO / p).is_dir() for p in BENCH["paths"])


def test_run_seconds_fits_the_full_check_with_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(WIDTH_SUFFIXES)     # depth only, no width
    data = json.loads((REPO / config["file"]).read_text())
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    # widths as published (Mistral-7B / Mixtral-8x7B config.json)
    assert (data["hidden_size"], data["intermediate_size"],
            data["num_attention_heads"], data["num_key_value_heads"],
            data["vocab_size"]) == (4096, 14336, 32, 8, 32000)
    assert data["num_hidden_layers"] < 32 and "deployment" in data
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


def test_configuration_files_are_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_finds_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    found = cells.find_cell(BENCH, cell["name"], REPO)
    assert found.traffic["kind"] in ("serve", "train")
    if found.traffic.get("loop") == "open":
        assert isinstance(found.traffic["rate_rps"], (int, float))
    reported = {m["name"] for m in found.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert found.per_layer
    for metric in found.per_layer:
        assert metric["moves"] in reported


def test_cells_are_distinct_and_four_chip_quota_holds():
    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names)
    combos = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(combos)) == len(combos)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(names) // 4)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry_has_a_reader(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    if "workloads" in metric:
        assert set(metric["workloads"]) <= {w["name"] for w in BENCH["workloads"]}
    reader = cells.load_module(REPO, BENCH, "metrics", metric["name"])
    assert reader is not None and callable(reader.read)


def test_metric_names_are_distinct_and_rooflines_are_percent():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    for m in metrics:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_unknown_cell_is_an_error():
    with pytest.raises(cells.CellError):
        cells.find_cell(BENCH, "no-such-cell", REPO)


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in BENCH["paths"]:
        for path in (REPO / base).rglob("*"):
            if "__pycache__" in path.parts:
                continue
            assert ok.match(str(path.relative_to(REPO))), path
