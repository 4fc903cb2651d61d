"""Share of the device's idle seconds in the traced window that a leaf phase
of the tick explains (a throughput cell: it moves tokens per second);
``harness/named_readers.py`` ``idle_attributed_share``."""

from benchmarks.harness import named_readers


def read(obs):
    return named_readers.idle_attributed_share(obs)
