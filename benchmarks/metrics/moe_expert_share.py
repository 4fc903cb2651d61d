"""Device time of the operations traced under the program's ``moe/experts``
scope (an operation's ``tf_op`` in the trace) over device busy time: the
expert matmuls and the combine, whatever shapes or kernel they use. Not
listed for ``joyai-flash-serve-docqa`` and the cells after it: when that cell
was defined (PR 31) XLA's grouped-matmul call carried its own name where the
scope would be, so the scope found a tenth of the experts' time there, and
those cells report ``moe_grouped_share``, which knows the call by name too."""

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
