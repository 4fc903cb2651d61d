"""Share of the steps that were dispatched while the one before was still
pending (``ahead`` 1 on the same spans ``tick_step_starved_share`` reads):
high while requests overlap, and what a loop that fell back to one step at a
time would pull down (a latency cell). ``harness/idle_readers.py``
``read_step_ahead_share``."""

from benchmarks.harness import idle_readers


def read(obs):
    return idle_readers.read_step_ahead_share(obs)
