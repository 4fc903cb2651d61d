"""90th percentile, over the requests due in the window, of the request's own
(last token - first token) / (tokens - 1): ISSUE 23's definition. Recorded,
not judged: over the chat cell's 51 requests it is the fifth-largest value
and spread by 8-9% across seeds (PERF.md, PR 23)."""

import numpy as np

from benchmarks.harness import readers


def read(obs):
    values = readers.tpot_s(obs)
    return float(np.percentile(values, 90) * 1e3) if len(values) else None
