"""Decode rows that rode in a chunk's step program over all decode rows, in
percent: sum of ``fused_rows`` (what the program puts on a tick's last
``serve/prefill_chunk`` span: the rows of the tick's decode batch that went
through the layers with that chunk's rows, one pass over the weights for
both) over that sum and the ``batch`` of the ``serve/step_decode`` spans (the
decode batches that had a program of their own), on the program's spans in
the window. None where the program stamps no ``fused_rows`` (every decode
batch has its own step program, as before PR 45) and, like the other shares
of the program's spans (``step_ahead_share``), on a run with no device in its
trace."""

from benchmarks.harness import idle_readers
from benchmarks.harness import program_events as pe


def read(obs):
    if not idle_readers.on_a_device(obs):
        return None
    evs = idle_readers.windowed(obs)
    fused = [e.arg("fused_rows") for e in evs if e.name == pe.PREFILL_CHUNK]
    fused = [n for n in fused if n is not None]
    if not fused:
        return None
    alone = sum(e.arg("batch") or 0 for e in evs if e.name == pe.STEP_DECODE)
    rows = sum(fused) + alone
    return 100.0 * sum(fused) / rows if rows else None
