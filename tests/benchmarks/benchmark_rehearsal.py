"""Tiny cells for the CPU: a temporary copy of ``BENCHMARK.json`` and
``benchmarks/`` with toy configurations, traffic mixes, cells, a model family
the benchmark has not run and a metric dropped in as files and entries alone,
which is also how a later PR adds them."""

import copy
import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"

#: (cell, configuration, traffic, the benchmark's cell whose metrics it
#: reports: its name is appended to every ``workloads`` list that names that)
TINY_CELLS = [
    ("tiny-train", "tiny-mistral", "tiny-pretrain", "mistral7b-train-8k"),
    ("tiny-train-x4", "tiny-mistral-zero3", "tiny-pretrain",
     "mistral7b-train-8k"),
    ("tiny-chat", "tiny-mistral", "tiny-chat", "mistral7b-serve-chat"),
    ("tiny-rag", "tiny-mixtral", "tiny-rag", "mixtral8x7b-serve-batch-rag"),
    ("tiny-moe-shared", "tiny-qwen2-moe", "tiny-rag",
     "mixtral8x7b-serve-batch-rag"),
]

#: What a family the harness has not run brings, beside its configuration
#: (``families/`` and ``reference/``), and a per-layer metric of its cell.
DROPPED_IN = ["families/qwen2_moe.py", "reference/qwen2_moe.py",
              "metrics/prefill_chunks_per_tick.py"]
DROPPED_IN_METRICS = [
    {"name": "prefill_chunks_per_tick", "unit": "chunks", "better": "lower",
     "source": "program_counter", "layer": "engine scheduler",
     "moves": "serve_tokens_per_s", "workloads": ["tiny-moe-shared"]}]


#: A family that joins after every family that is there, as
#: ``benchmarks/README.md``'s table prescribes: a configuration, a served and
#: a training cell on traffic the benchmark has, and one per-layer metric
#: (``metrics/later_family_share.py`` beside this file). Nothing end to end.
LATER_CONFIG = {
    "name": "later-family-d6",
    "source": json.loads((DATA / "later-family.json").read_text())["source"],
    "file": "tests/benchmarks/data/later-family.json",
    "reduced": ["num_hidden_layers"], "why": "made up: a later family"}
LATER_SERVED = {"name": "later-family-serve-batch-rag",
                "config": "later-family-d6", "traffic": "batch-rag",
                "chips": 1, "why": "made up: a later family's served cell"}
LATER_TRAINED = {"name": "later-family-train-8k", "config": "later-family-d6",
                 "traffic": "pretrain-8k", "chips": 1,
                 "why": "made up: a later family's training cell"}
LATER_METRIC = {"name": "later_family_share", "unit": "%", "better": "higher",
                "source": "device_trace", "layer": "model step",
                "moves": "serve_tokens_per_s",
                "workloads": [LATER_SERVED["name"]]}
#: the lists every cell of a kind is in: a later cell of that kind joins them
EVERY_SERVED_CELLS = ("serve_tokens_per_s", "idle_with_work_share",
                      "step_starved_share", "step_ahead_share",
                      "host_gc_pause_max_ms")
EVERY_TRAINING_CELLS = ("train_tokens_per_s_per_chip", "train_mfu")


def with_a_later_family(bench: dict) -> dict:
    """A deep copy of ``bench`` with a made-up family appended LAST: its
    configuration, its two cells, its per-layer metric, and each cell last in
    every list its kind of cell reports. What a test says of the file's
    entries it says of this copy too (``conftest.py``'s ``bench`` fixture),
    so a test that pins where an entry stands, who else is in a list or how
    many entries there are fails in the PR that writes it, and not in the
    next family's."""
    later = copy.deepcopy(bench)
    later["configs"].append(dict(LATER_CONFIG))
    later["workloads"] += [dict(LATER_SERVED), dict(LATER_TRAINED)]
    later["per_layer"].append(copy.deepcopy(LATER_METRIC))
    by_name = {m["name"]: m for m in later["end_to_end"] + later["per_layer"]}
    for names, cell in ((EVERY_SERVED_CELLS, LATER_SERVED),
                        (EVERY_TRAINING_CELLS, LATER_TRAINED)):
        for name in names:
            by_name[name]["workloads"].append(cell["name"])
    return later


def with_tiny_cells(bench: dict) -> dict:
    """A deep copy of ``bench`` with the toy configurations, cells and the
    dropped-in metric appended as entries: what ``tiny_root`` writes beside
    the files it copies."""
    bench = copy.deepcopy(bench)
    for config in sorted({c for _, c, _, _ in TINY_CELLS}):
        data = json.loads((DATA / f"{config}.json").read_text())
        bench["configs"].append({
            "name": config, "source": data["source"],
            "reduced": data["reduced"], "why": "toy",
            "file": f"benchmarks/configs/{config}.json"})
    bench["per_layer"] += copy.deepcopy(DROPPED_IN_METRICS)
    for cell, config, mix, like in TINY_CELLS:
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": mix, "why": "toy",
                                   "chips": 4 if cell.endswith("-x4") else 1})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if like in metric.get("workloads", ()):
                metric["workloads"] = metric["workloads"] + [cell]
    return bench


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped directory holding the benchmark plus the tiny cells.
    Nothing that was there is edited: files are added under ``configs/``,
    ``traffic/``, ``families/``, ``reference/`` and ``metrics/``, entries are
    appended to the lists of ``BENCHMARK.json``."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for config in sorted({c for _, c, _, _ in TINY_CELLS}):
        shutil.copy(DATA / f"{config}.json",
                    root / "benchmarks" / "configs" / f"{config}.json")
    for rel in DROPPED_IN:
        shutil.copy(DATA / rel, root / "benchmarks" / rel)
    for _, _, mix, _ in TINY_CELLS:
        shutil.copy(DATA / f"{mix}.json",
                    root / "benchmarks" / "traffic" / f"{mix}.json")
    bench = with_tiny_cells(json.loads((REPO / "BENCHMARK.json").read_text()))
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def files_that_differ(root: pathlib.Path) -> list:
    """The files of the repo's ``benchmarks/`` that ``root`` lacks or holds
    with other bytes: what a PR that only adds leaves empty."""
    out = []
    for path in sorted((REPO / "benchmarks").rglob("*")):
        rel = path.relative_to(REPO)
        if path.is_file() and "__pycache__" not in path.parts and not (
                (root / rel).is_file()
                and (root / rel).read_bytes() == path.read_bytes()):
            out.append(str(rel))
    return out


def run_cell(root: pathlib.Path, name: str, seconds: float, trace: bool,
             seed: int = 3, lines=None):
    """One tiny cell on the CPU's first device, through the same runner and
    result code as ``benchmarks/run.py`` (which itself refuses the CPU)."""
    import importlib
    import time

    import jax

    from benchmarks.harness import cells, result
    start = time.monotonic()
    bench = cells.load_benchmark(root)
    cell = cells.find_cell(bench, name, root)
    devices = jax.devices()[:cell.chips]
    say = (lines.append if lines is not None else (lambda text: None))
    runner = importlib.import_module(
        f"benchmarks.harness.run_{cell.traffic['kind']}")
    obs = runner.run(cell, bench, devices, seed, seconds, trace, say)
    obs.process_start = start
    return obs, json.loads(result.result_line(cell, bench, obs, trace, say))
