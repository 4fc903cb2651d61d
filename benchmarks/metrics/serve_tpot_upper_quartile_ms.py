"""Upper quartile (p75) of the time between two consecutive tokens of a
stream, over every token gap of every request due in the window (some ten
thousand gaps in the chat cell). Recorded, not judged: it was the chat cell's
end-to-end ``serve_tpot_p75_ms`` from PR 23 to PR 28, chosen because the gaps
were two populations, decode-only ticks (46-52 ms) and the 5-10% that also
carry a prefill (60-220 ms), and the p75 lay on the plateau between the p50
and the p90. Since PR 28 the tick is 8.5 ms, about two streams are live at
the cell's rate, and a tick's length steps with the decode batch bucket (1-2,
4, 8 sequences): the p75 now falls on the step between the first two and
reads 9.8-10.1 or 10.4-11.0 ms by the seed, the same in a second process
(fourteen seeds, two sets of six among them: 9.6% and 10.1% between the
quartiles, PERF.md, PR 29). ``serve_tpot_p50_ms`` lies on the plateau."""

import numpy as np

from benchmarks.harness import readers


def read(obs):
    gaps = readers.token_gaps_s(obs)
    return float(np.percentile(gaps, 75) * 1e3) if len(gaps) else None
