"""Sequences decoded over the rows the decode batches were padded to: sum of
``batch`` over sum of ``bucket`` (what ``snap_bucket`` returned) on the
program's ``serve/step_decode`` spans in the window."""

from benchmarks.harness import program_events as pe


def read(obs):
    evs = pe.inside(pe.events(), obs.window, obs.outside_stall)
    return pe.fill(evs, pe.STEP_DECODE, "batch", "bucket")
