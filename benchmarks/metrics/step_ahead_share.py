"""Share of the steps that were dispatched while the one before was still
pending (``ahead`` 1 on the same spans ``step_starved_share`` reads): near
100 under load, and what a loop that fell back to one step at a time would
pull down (a throughput cell). ``harness/idle_readers.py``
``read_step_ahead_share``."""

from benchmarks.harness import idle_readers


def read(obs):
    return idle_readers.read_step_ahead_share(obs)
