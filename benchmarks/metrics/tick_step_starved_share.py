"""Of the steps dispatched while the one before was in flight, the share that
found it already finished (``starved`` 1 on the tick's ``serve/step_decode``,
or its last ``serve/prefill_chunk`` where it decoded nothing): the device
had run dry for want of the host, so the host set that step's pace (a latency
cell). ``harness/idle_readers.py`` ``read_step_starved_share``."""

from benchmarks.harness import idle_readers


def read(obs):
    return idle_readers.read_step_starved_share(obs)
