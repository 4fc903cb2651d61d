"""``BENCHMARK.json`` against the contract's limits, and every name in it
against a file the harness can find: of the file as it is and of a copy with
a later family appended (``conftest.py``'s ``bench``), and the guard that
keeps every other test of the entries to that fixture."""

import ast
import json
import pathlib
import re

import pytest

import benchmark_rehearsal as rehearsal
from benchmarks.harness import cells

REPO = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def entries(*lists):
    """One case an entry of ``lists``: the file's own under the ids they
    always had, then the later family's. Each case hands its test the file
    the entry stands in as ``bench``, in the fixture's place."""
    later = rehearsal.with_a_later_family(
        json.loads((REPO / "BENCHMARK.json").read_text()))
    return [pytest.param(later, entry, id=entry["name"])
            for key in lists for entry in later[key]]


def test_top_level_keys_and_size(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert len(json.dumps(bench, indent=2)) <= 64 * 1024
    assert 1 <= len(bench["command"]) <= 32
    assert 1 <= len(bench["paths"]) <= 16
    assert all((REPO / p).is_dir() for p in bench["paths"])


def test_run_seconds_fits_the_full_check_with_24_cells(bench):
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


# --- the rule for a configuration -------------------------------------------
# What may be cut from the source (model-configs guide, section 4): how many
# layers, and the chip's share of a stated deployment: how many routed
# experts, heads or rows of the vocabulary are held here. Never a width.
COUNT_SUFFIXES = ("_layers", "_experts", "_heads")
WIDTH_SUFFIXES = ("_size", "_dim", "_rank", "_per_tok", "_width")
DEPTH_KEYS = ("num_hidden_layers", "num_layers", "n_layers")
LEADING_DENSE_KEYS = ("first_k_dense_replace", "num_dense_layers",
                      "n_dense_first_layers")
MIN_EXPERTS_HELD, MIN_VOCAB_SHARE, MIN_LAYERS_AFTER_DENSE = 8, 8, 4

#: The sources whose widths this test can hold to numbers. For any other
#: source it compares nothing with numbers it cannot have: that the widths
#: are the source's is then the reviewer's to check against the catalog.
MISTRAL_WIDTHS = {"hidden_size": 4096, "intermediate_size": 14336,
                  "num_attention_heads": 32, "num_key_value_heads": 8,
                  "vocab_size": 32000}
PUBLISHED_WIDTHS = {
    "https://huggingface.co/mistralai/Mistral-7B-v0.1/blob/main/config.json":
        MISTRAL_WIDTHS,
    "https://huggingface.co/mistralai/Mixtral-8x7B-v0.1/blob/main/config.json":
        MISTRAL_WIDTHS,
}

#: The three configurations of PR 23 predate the floors (2, 8 and 3 layers:
#: as deep as their weights, optimizer state and KV pool leave room for on
#: one chip); their cells' bounds stand on those depths, so they stay.
PREDATE_THE_FLOORS = {"mistral-7b-train-d2", "mistral-7b-serve-d8",
                      "mixtral-8x7b-serve-d3"}


def is_width(key: str) -> bool:
    return key.endswith(WIDTH_SUFFIXES) and key != "vocab_size"


def may_be_cut(key: str) -> bool:
    return key.endswith(COUNT_SUFFIXES) or key == "vocab_size"


def configuration_faults(entry: dict, data: dict, floors: bool = True) -> list:
    """The ways in which a ``configs`` entry and its file's data break the
    rule, as sentences; empty where they keep it."""
    faults = []
    if set(entry) != {"name", "source", "file", "reduced", "why"}:
        faults.append(f"entry keys: {sorted(entry)}")
        return faults
    reduced = entry["reduced"]
    if not NAME.match(entry["name"]):
        faults.append(f"name: {entry['name']!r}")
    if len(reduced) > 16 or not all(NAME.match(k) for k in reduced):
        faults.append("reduced: over 16 keys, or a key that is no name")
    if data.get("source") != entry["source"]:
        faults.append("source: the file's differs from the entry's")
    if data.get("reduced") != reduced:
        faults.append("reduced: the file's differs from the entry's")
    if "deployment" not in data:
        faults.append("deployment: the file does not say what it stands for")
    published = data.get("published")
    if not isinstance(published, dict):
        faults.append("published: missing; the file states the source's "
                      "number for every key of reduced")
        published = {}
    elif set(published) != set(reduced):
        faults.append(f"published: keys {sorted(published)} are not exactly "
                      f"reduced's {sorted(reduced)}")
    for key in sorted(set(reduced) | set(published)):
        if is_width(key):
            faults.append(f"width: {key} is a width, and no width is cut")
        elif not may_be_cut(key):
            faults.append(f"outside the list: {key} counts neither layers, "
                          f"experts, heads nor the vocabulary")
    for key in reduced:
        here, there = data.get(key), published.get(key)
        if key in published and not (
                isinstance(here, int) and isinstance(there, int)
                and 0 < here < there):
            faults.append(f"not smaller: {key} is {here!r} here and "
                          f"{there!r} published")
    depth_key = next((k for k in DEPTH_KEYS if k in data), None)
    if "layer_types" in data and depth_key is not None and \
            len(data["layer_types"]) != data[depth_key]:
        faults.append(f"layer_types: {len(data['layer_types'])} entries for "
                      f"{data[depth_key]} layers")
    share = [k for k in reduced if not k.endswith("_layers")]
    if share and not (isinstance(data.get("deployment_chips"), int)
                      and data["deployment_chips"] >= 2):
        faults.append(f"deployment_chips: {share} are a chip's share, and the "
                      f"file does not say of how many chips")
    if faults or not floors:
        return faults       # the floors judge a cut that is otherwise sound
    for key in reduced:
        if key.endswith("_experts") and data[key] < MIN_EXPERTS_HELD:
            faults.append(f"floor: {data[key]} experts held ({key}), under "
                          f"{MIN_EXPERTS_HELD}")
    if "vocab_size" in reduced and \
            data["vocab_size"] * MIN_VOCAB_SHARE < published["vocab_size"]:
        faults.append(f"floor: {data['vocab_size']} rows are under an "
                      f"eighth of the vocabulary's {published['vocab_size']}")
    if depth_key in reduced:
        dense = next((data[k] for k in LEADING_DENSE_KEYS if k in data), 0)
        if data[depth_key] - dense < MIN_LAYERS_AFTER_DENSE:
            faults.append(f"floor: {data[depth_key] - dense} layers after "
                          f"{dense} leading dense, under "
                          f"{MIN_LAYERS_AFTER_DENSE}")
    return faults


@pytest.mark.parametrize("bench,config", entries("configs"))
def test_configuration_entry_and_file(bench, config):
    assert any(config["file"].startswith(p + "/") for p in bench["paths"])
    data = json.loads((REPO / config["file"]).read_text())
    assert configuration_faults(
        config, data, floors=config["name"] not in PREDATE_THE_FLOORS) == []
    # widths as published, where the table has the source's numbers
    for key, value in PUBLISHED_WIDTHS.get(config["source"], {}).items():
        assert key in config["reduced"] or data[key] == value, key
    assert any(w["config"] == config["name"] for w in bench["workloads"])


def made_up(**changes):
    """(entry, file's data) of a share cut that keeps every floor: one chip
    of eight that share each layer of a model with a leading dense layer, 64
    routed experts and 102,400 rows. ``changes`` are laid over the data
    (None takes a key out); ``reduced`` and ``source`` go to the entry too."""
    data = {"source": "https://example.org/made-up/config.json",
            "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
            "published": {"num_hidden_layers": 60, "n_routed_experts": 64,
                          "vocab_size": 102400},
            "deployment": "one chip of eight that share each layer",
            "deployment_chips": 8, "hidden_size": 5120, "kv_lora_rank": 512,
            "moe_intermediate_size": 1536, "num_attention_heads": 128,
            "num_experts_per_tok": 6, "first_k_dense_replace": 1,
            "num_hidden_layers": 5, "n_routed_experts": 8,
            "vocab_size": 12800}
    data.update(changes)
    data = {k: v for k, v in data.items() if v is not None}
    entry = {"name": "made-up", "file": "benchmarks/configs/made-up.json",
             "why": "a case of the rule", "source": data["source"],
             "reduced": data["reduced"]}
    return entry, data


def reduce_also(key, here, there):
    """Changes that cut one more key, from ``there`` to ``here``."""
    _, data = made_up()
    return {"reduced": data["reduced"] + [key], key: here,
            "published": dict(data["published"], **{key: there})}


MADE_UP_CASES = [
    # changes to the sound case, the one fault it must show or None
    pytest.param({}, None, id="share-cut-keeping-the-floors"),
    pytest.param({"reduced": ["num_hidden_layers"], "deployment_chips": None,
                  "published": {"num_hidden_layers": 60}}, None,
                 id="depth-alone-needs-no-deployment-chips"),
    pytest.param(reduce_also("num_attention_heads", 32, 128), None,
                 id="heads-may-be-a-share"),
    pytest.param(reduce_also("kv_lora_rank", 256, 512), "width:",
                 id="width-in-reduced-rank"),
    pytest.param(reduce_also("moe_intermediate_size", 768, 1536), "width:",
                 id="width-in-reduced-size"),
    pytest.param(reduce_also("num_experts_per_tok", 2, 6), "width:",
                 id="width-in-reduced-per-tok"),
    pytest.param({"published": None}, "published: missing",
                 id="published-missing"),
    pytest.param({"published": {"num_hidden_layers": 60,
                                "n_routed_experts": 64}}, "published: keys",
                 id="published-lacks-a-key"),
    pytest.param({"published": {"num_hidden_layers": 60, "n_routed_experts": 8,
                                "vocab_size": 102400}}, "not smaller:",
                 id="published-not-larger"),
    pytest.param({"n_routed_experts": 4}, "floor: 4 experts",
                 id="four-experts-held"),
    pytest.param({"vocab_size": 10240}, "floor: 10240 rows",
                 id="a-tenth-of-the-vocabulary"),
    pytest.param({"num_hidden_layers": 4},
                 "floor: 3 layers after 1 leading dense",
                 id="three-layers-after-one-dense"),
    pytest.param(reduce_also("max_position_embeddings", 4096, 163840),
                 "outside the list:", id="key-outside-the-list"),
    pytest.param({"deployment_chips": None}, "deployment_chips:",
                 id="share-without-deployment-chips"),
    pytest.param({"layer_types": ["full_attention"] * 4}, "layer_types:",
                 id="layer-types-of-another-depth"),
]


@pytest.mark.parametrize("changes,fault", MADE_UP_CASES)
def test_rule_on_made_up_configurations(changes, fault):
    """Each made-up case passes, or fails by exactly the fault it names."""
    faults = configuration_faults(*made_up(**changes))
    if fault is None:
        assert faults == []
    else:
        assert len(faults) == 1 and faults[0].startswith(fault), faults


def test_rule_holds_the_file_to_its_entry():
    entry, data = made_up()
    faults = configuration_faults(dict(entry, source="https://example.org/x",
                                       reduced=["num_hidden_layers"]), data)
    assert [f.split(":")[0] for f in faults] == ["source", "reduced",
                                                 "published"]


def test_configuration_files_are_distinct(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("bench,cell", entries("workloads"))
def test_cell_entry_finds_its_files(bench, cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    found = cells.find_cell(bench, cell["name"], REPO)
    assert found.traffic["kind"] in ("serve", "train")
    if found.traffic.get("loop") == "open":
        assert isinstance(found.traffic["rate_rps"], (int, float))
    reported = {m["name"] for m in found.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert found.per_layer
    for metric in found.per_layer:
        assert metric["moves"] in reported


def test_cells_are_distinct_and_four_chip_quota_holds(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    combos = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(combos)) == len(combos)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(names) // 4)


@pytest.mark.parametrize("bench,metric", entries("end_to_end", "per_layer"))
def test_metric_entry_has_a_reader(bench, metric):
    end_to_end = metric in bench["end_to_end"]
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
        assert metric["moves"] in {m["name"] for m in bench["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    if "workloads" in metric:
        assert set(metric["workloads"]) <= {w["name"] for w in bench["workloads"]}
    reader = cells.load_module(REPO, bench, "metrics", metric["name"])
    assert reader is not None and callable(reader.read)


def test_metric_names_are_distinct_and_rooflines_are_percent(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    for m in metrics:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_unknown_cell_is_an_error(bench):
    with pytest.raises(cells.CellError):
        cells.find_cell(bench, "no-such-cell", REPO)


def test_files_under_paths_are_named_from_name_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in bench["paths"]:
        for path in (REPO / base).rglob("*"):
            if "__pycache__" in path.parts:
                continue
            assert ok.match(str(path.relative_to(REPO))), path


# --- what a test under tests/benchmarks/ may say of the file ------------------
# Its own entries exist and hold their own values; its cell is IN a list. Not
# where an entry stands, who else is in a list, or how many entries there are:
# a later family appends, and three families' tests in a row (PRs 31, 39, 41)
# closed the door behind them. ``conftest.py``'s ``bench`` runs every such
# test against a copy with a family appended; this holds the tests to it.

LISTS = ("configs", "workloads", "per_layer", "end_to_end")
TEST_FILES = sorted((REPO / "tests" / "benchmarks").glob("*.py"))


def _keyed(node, keys) -> bool:
    return isinstance(node, ast.Subscript) \
        and isinstance(node.slice, ast.Constant) and node.slice.value in keys


def pins(source: str) -> list:
    """(line, what is wrong) for every place where a test file reads
    ``BENCHMARK.json``'s entries round the fixture or pins what a later
    family changes: a test that indexes a module-level read of the file (or
    of ``cells.load_benchmark``, or a name made from one); an index or a
    slice by position into one of the file's lists or a metric's
    ``workloads``; a metric's ``workloads`` compared with ``==`` or ``!=``,
    or a cell said to be ``not in`` it."""
    tree = ast.parse(source)
    read = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            text = ast.get_source_segment(source, node.value) or ""
            made_from = {n.id for n in ast.walk(node.value)
                         if isinstance(n, ast.Name)}
            if "BENCHMARK.json" in text or "load_benchmark" in text \
                    or made_from & read:
                read |= {t.id for t in node.targets
                         if isinstance(t, ast.Name)}
    out = []
    for fn in ast.walk(tree):
        if isinstance(fn, ast.FunctionDef) and fn.name.startswith("test_"):
            out += [(n.lineno, f"{fn.name} indexes the module-level read "
                               f"{n.value.id}: take ``bench`` from the fixture")
                    for n in ast.walk(fn) if isinstance(n, ast.Subscript)
                    and isinstance(n.value, ast.Name) and n.value.id in read]
    for node in ast.walk(tree):
        if isinstance(node, ast.Subscript) and _keyed(node.value, LISTS) \
                and not isinstance(node.slice, (ast.Name, ast.Attribute)):
            out.append((node.lineno, f"an entry of {node.value.slice.value!r} "
                                     f"taken by position"))
        if isinstance(node, ast.Compare) and any(
                isinstance(op, (ast.Eq, ast.NotEq, ast.NotIn))
                for op in node.ops) and any(
                _keyed(n, ("workloads",))
                for side in [node.left] + node.comparators
                for n in ast.walk(side)):
            out.append((node.lineno, "a metric's workloads compared with "
                                     "==, != or not in: say that a cell is IN"))
    return sorted(set(out))


@pytest.mark.parametrize("path", TEST_FILES, ids=lambda p: p.name)
def test_no_test_pins_a_position_or_steps_round_the_fixture(path):
    assert pins(path.read_text()) == []


HEAD = 'import json\nBENCH = json.loads(open("BENCHMARK.json").read())\n'
PINNED = [
    pytest.param(HEAD + 'def test_x():\n    assert BENCH["configs"]\n',
                 "indexes the module-level read BENCH", id="module-level-read"),
    pytest.param(HEAD + 'LATER = dict(BENCH)\ndef test_x():\n'
                 '    by_name = {m["name"]: m for m in LATER["per_layer"]}\n'
                 '    assert by_name\n',
                 "indexes the module-level read LATER", id="a-name-made-from-it"),
    pytest.param('from benchmarks.harness import cells\n'
                 'B = cells.load_benchmark()\ndef test_x():\n'
                 '    assert B["paths"]\n',
                 "indexes the module-level read B", id="the-loader"),
    pytest.param('def test_x(bench):\n'
                 '    assert bench["configs"][-1]["name"] == "mine"\n',
                 "'configs' taken by position", id="last-configuration"),
    pytest.param('def test_x(bench):\n'
                 '    assert [m["name"] for m in bench["per_layer"][-2:]]\n',
                 "'per_layer' taken by position", id="last-two-metrics"),
    pytest.param('def test_x(tokens):\n'
                 '    assert tokens["workloads"][-1] == "mine"\n',
                 "'workloads' taken by position", id="last-cell-of-a-list"),
    pytest.param('def test_x(by_name):\n'
                 '    assert by_name["m"]["workloads"] == ["mine"]\n',
                 "compared with", id="a-list-closed-with-=="),
    pytest.param('def test_x(a, b):\n'
                 '    assert "theirs" not in a["workloads"] + b["workloads"]\n',
                 "compared with", id="a-cell-said-to-be-absent"),
]


@pytest.mark.parametrize("source,fault", PINNED)
def test_guard_finds_each_pin_it_is_there_for(source, fault):
    found = pins(source)
    assert found and all(fault in what for _, what in found[-1:]), found


def test_guard_passes_membership_and_own_values():
    assert pins(HEAD + 'from benchmarks.harness import cells\n'
                'def test_x(bench, tmp_path):\n'
                '    by_name = {m["name"]: m for m in bench["per_layer"]}\n'
                '    assert "mine" in by_name["m"]["workloads"]\n'
                '    assert by_name["m"]["moves"] == "serve_tokens_per_s"\n'
                '    entry, = [c for c in bench["configs"]\n'
                '              if c["name"] == "mine"]\n'
                '    assert entry["reduced"] == ["num_hidden_layers"]\n'
                '    assert set(["a"]) <= set(by_name["m"]["workloads"])\n'
                '    assert cells.load_module(tmp_path, BENCH, "metrics", "m")\n'
                ) == []
