"""Median host time per decode tick in which the device has nothing to run:
from the end of the tick's ``serve/decode_wait`` (the tokens are back, the
queue is empty) to the start of the next tick's first dispatch span
(``serve/decode_dispatch`` or ``serve/prefill_chunk``). Taken from the
program's own spans, joined by their ``tick``."""

import numpy as np

from benchmarks.harness import program_events as pe


def read(obs):
    evs = pe.inside(pe.loop_thread(pe.events()), obs.window,
                    obs.outside_stall)
    bubbles = pe.decode_bubbles(evs)
    return float(np.median(bubbles) * 1e3) if bubbles else None
