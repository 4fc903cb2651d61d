"""The per-layer metric of the made-up family that
``tests/benchmarks/benchmark_rehearsal.py`` ``with_a_later_family`` appends
to a copy of ``BENCHMARK.json``. No cell of the benchmark names it and no run
reads it: it is there so that the copy's entry has a reader to find."""


def read(obs):
    return None
