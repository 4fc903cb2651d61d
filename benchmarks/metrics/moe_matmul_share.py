"""Device time of the expert-matmul fusions over device busy time. An
operation is an expert matmul when one of its operands has the stacked expert
weights' shape, [experts, hidden, ff] or [experts, ff, hidden], which the
trace gives in the instruction's text."""

import re

from benchmarks.harness import readers
from benchmarks.harness import trace as tr


def read(obs):
    ops = readers.compute_ops(obs)
    hf = obs.cell.config
    if not ops or "num_local_experts" not in hf:
        return None
    e, d, f = hf["num_local_experts"], hf["hidden_size"], hf["intermediate_size"]
    shape = re.compile(rf"\[{e},{d},{f}\]|\[{e},{f},{d}\]")
    busy = tr.busy_seconds(obs.trace, obs.trace.window)
    expert = sum(o.dur for o in ops if shape.search(o.detail))
    return 100.0 * expert / len(obs.trace.devices) / busy if busy else None
