"""Prompt tokens prefilled over the rows their chunks were padded to: sum of
``tokens`` over sum of ``bucket`` on the program's ``serve/prefill_chunk``
spans in the window."""

from benchmarks.harness import program_events as pe


def read(obs):
    evs = pe.inside(pe.events(), obs.window, obs.outside_stall)
    return pe.fill(evs, pe.PREFILL_CHUNK, "tokens", "bucket")
