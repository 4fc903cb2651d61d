"""Median time between two consecutive tokens of a stream, over every token
gap of every request due in the window (some ten thousand gaps in the chat
cell): the decode tick as a reader of a stream sees it. The median and not
the upper quartile since PR 29: a tick's length steps with the decode batch
bucket, at the cell's rate about two streams are live, and over fourteen
seeds the p75 falls on the step between the first two buckets (9.8-11.0 ms,
9.6% and 10.1% between the quartiles of two sets of six seeds) where the
median lies on the first plateau (8.83-9.63 ms; 3.9% and 2.7%; a seed repeats
to 1%). A request that did not get all its tokens enters with each gap it
asked for at (end of run - due), as in every latency statistic here.
``serve_tpot_upper_quartile_ms``, ``serve_tpot_request_p90_ms`` and
``serve_tpot_mean_ms`` beside it hold the slower ticks and the stalls."""

import numpy as np

from benchmarks.harness import readers


def read(obs):
    gaps = readers.token_gaps_s(obs)
    return float(np.percentile(gaps, 50) * 1e3) if len(gaps) else None
