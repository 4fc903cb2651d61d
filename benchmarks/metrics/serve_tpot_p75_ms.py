"""Upper quartile of the time between two consecutive tokens of a stream, over
every token gap of every request due in the window (some ten thousand gaps in
the chat cell): the decode tick as the streams see it under the load's busier
half. The p75 and not the p90: the gaps are two populations, decode-only
ticks (46-52 ms) and the 5-10% of ticks that also carry a prefill (60-220
ms); the p90 falls on the step between them and read 51.4 or 62.5 ms by the
seed, where the p75 lies on the plateau from the p50 to the p90 and spread
by 0.5-1.6% (PERF.md, PR 23). It leaves the plateau when over a quarter of the
gaps are slow: one seed in the nine tried deals more than 16 live streams for
a quarter of the window, the decode batch snaps to the 32 bucket (62 ms a
tick) and the p75 read 61.4 ms; the schedule's arithmetic puts 1% of seeds
there. ``serve_tpot_mean_ms`` beside it holds the stalls."""

import numpy as np

from benchmarks.harness import readers


def read(obs):
    gaps = readers.token_gaps_s(obs)
    return float(np.percentile(gaps, 75) * 1e3) if len(gaps) else None
