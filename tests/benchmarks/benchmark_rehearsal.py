"""Tiny cells for the CPU: a temporary copy of ``BENCHMARK.json`` and
``benchmarks/`` with toy configurations, traffic mixes and cells dropped in
as files and entries alone — which is also how a later PR adds them."""

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"

TINY_CELLS = [
    ("tiny-train", "tiny-mistral", "tiny-pretrain"),
    ("tiny-train-x4", "tiny-mistral-zero3", "tiny-pretrain"),
    ("tiny-chat", "tiny-mistral", "tiny-chat"),
    ("tiny-rag", "tiny-mixtral", "tiny-rag"),
]


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped directory holding the benchmark plus the tiny cells.
    Nothing that was there is edited: files are added under ``configs/`` and
    ``traffic/``, entries are appended to ``BENCHMARK.json``."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for config in sorted({c for _, c, _ in TINY_CELLS}):
        shutil.copy(DATA / f"{config}.json",
                    root / "benchmarks" / "configs" / f"{config}.json")
        bench["configs"].append({
            "name": config, "source": "tests", "reduced": [], "why": "toy",
            "file": f"benchmarks/configs/{config}.json"})
    for cell, config, mix in TINY_CELLS:
        shutil.copy(DATA / f"{mix}.json",
                    root / "benchmarks" / "traffic" / f"{mix}.json")
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": mix, "why": "toy",
                                   "chips": 4 if cell.endswith("-x4") else 1})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            old = metric["workloads"]
            if any("train" in w for w in old):
                metric["workloads"] = old + ["tiny-train", "tiny-train-x4"]
            elif any("chat" in w for w in old):
                metric["workloads"] = old + ["tiny-chat"]
            else:
                metric["workloads"] = old + ["tiny-rag"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


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
