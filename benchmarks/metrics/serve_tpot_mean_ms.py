"""Mean time between two consecutive tokens of a stream, over every token gap
of every request due in the window: all the decode work of the window's
requests over all their tokens, the stalls behind other requests' prefills
included. Recorded, not judged: it follows how the seed's arrivals bunch
(52.0, 53.1 and 54.2 ms for three seeds, each repeated to 0.8%; PERF.md,
PR 23)."""

from benchmarks.harness import readers


def read(obs):
    gaps = readers.token_gaps_s(obs)
    return float(gaps.mean() * 1e3) if len(gaps) else None
