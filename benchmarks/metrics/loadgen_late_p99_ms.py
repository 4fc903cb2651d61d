"""How late the generator ran: sent - due, 99th percentile over the measured
requests due outside the profiler's start and stop."""

import numpy as np


def read(obs):
    late = [r.sent - r.due for r in obs.requests
            if r.measured and r.sent is not None and obs.outside_stall(r.due)]
    return float(np.percentile(late, 99) * 1e3) if late else None
