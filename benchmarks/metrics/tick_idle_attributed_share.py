"""Share of the device's idle seconds in the traced window that a leaf phase
of the tick explains (a latency cell: it moves the time between tokens);
``harness/named_readers.py`` ``idle_attributed_share``."""

from benchmarks.harness import named_readers


def read(obs):
    return named_readers.idle_attributed_share(obs)
