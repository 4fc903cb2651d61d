"""Share of the traced window in which the device was idle and the serve loop
was not waiting for work: idle gaps whose midpoint lies in no ``serve/idle``
span, over the window's seconds (a latency cell: it moves the time between
tokens). The idle the program answers for; what lies under ``serve/idle`` is
the traffic's. ``harness/idle_readers.py`` ``read_idle_with_work_share``."""

from benchmarks.harness import idle_readers


def read(obs):
    return idle_readers.read_idle_with_work_share(obs)
