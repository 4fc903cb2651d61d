"""One cell, once, in a new process:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses anything but a TPU with the chips the cell asks for (nonzero exit, no
result line), builds weights on the device from ``--seed``, warms up, measures
for ``--seconds``, and prints the contract's one JSON object as the last line
of stdout. Every line before it names the platform, the device kind and the
device count. Which cell, configuration, traffic mix and metrics exist is
data: ``BENCHMARK.json`` and the files it names (see ``README.md``).
"""

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmarks/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmarks.harness import cells, device, result
    bench = cells.load_benchmark(ROOT)
    cell = cells.find_cell(bench, args.workload, ROOT)
    devices = device.require_chips(cell.chips, "benchmarks/run.py")
    say = device.Say(devices)
    cache = device.enable_compile_cache(ROOT)
    clock = device.CompileClock()
    say(f"cell {cell.name}: configuration {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {args.seed}, {args.seconds} s, "
        f"trace {args.trace}; compile cache {cache}")
    runner = importlib.import_module(
        f"benchmarks.harness.run_{cell.traffic['kind']}")
    obs = runner.run(cell, bench, devices, args.seed, args.seconds,
                     bool(args.trace), say)
    obs.process_start = PROCESS_START
    say(f"whole run: {clock}")
    line = result.result_line(cell, bench, obs, bool(args.trace), say)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
