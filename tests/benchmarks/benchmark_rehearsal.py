"""Tiny cells for the CPU: a temporary copy of ``BENCHMARK.json`` and
``benchmarks/`` with toy configurations, traffic mixes, cells, a model family
the benchmark has not run and a metric dropped in as files and entries alone,
which is also how a later PR adds them."""

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


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped directory holding the benchmark plus the tiny cells.
    Nothing that was there is edited: files are added under ``configs/``,
    ``traffic/``, ``families/``, ``reference/`` and ``metrics/``, entries are
    appended to the lists of ``BENCHMARK.json``."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for config in sorted({c for _, c, _, _ in TINY_CELLS}):
        shutil.copy(DATA / f"{config}.json",
                    root / "benchmarks" / "configs" / f"{config}.json")
        data = json.loads((DATA / f"{config}.json").read_text())
        bench["configs"].append({
            "name": config, "source": data["source"],
            "reduced": data["reduced"], "why": "toy",
            "file": f"benchmarks/configs/{config}.json"})
    for rel in DROPPED_IN:
        shutil.copy(DATA / rel, root / "benchmarks" / rel)
    bench["per_layer"] += DROPPED_IN_METRICS
    for cell, config, mix, like in TINY_CELLS:
        shutil.copy(DATA / f"{mix}.json",
                    root / "benchmarks" / "traffic" / f"{mix}.json")
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": mix, "why": "toy",
                                   "chips": 4 if cell.endswith("-x4") else 1})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if like in metric.get("workloads", ()):
                metric["workloads"] = metric["workloads"] + [cell]
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
