"""Device time of the operations traced under the program's ``moe/experts``
scope (an operation's ``tf_op`` in the trace) over device busy time: the
expert matmuls and the combine, whatever shapes or kernel they use."""

from benchmarks.harness import named_readers
from benchmarks.harness import trace as tr

SCOPE = "/moe/experts/"


def read(obs):
    found = named_readers.named_ops(obs)
    if found is None:
        return None
    ops, devices, _ = found
    expert = sum(o.dur for o in ops if SCOPE in o.scope)
    busy = tr.busy_seconds(obs.trace, obs.trace.window)
    if not expert or not busy:
        return None
    return 100.0 * expert / len(devices) / busy
