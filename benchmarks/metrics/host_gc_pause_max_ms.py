"""The longest collection of the interpreter's in the measured window, any
thread (``host/gc``): every thread of the process stands still for it, the
serve loop among them (a throughput cell). 0.0 where none was emitted.
``harness/idle_readers.py`` ``read_host_gc_pause_max_ms``."""

from benchmarks.harness import idle_readers


def read(obs):
    return idle_readers.read_host_gc_pause_max_ms(obs)
