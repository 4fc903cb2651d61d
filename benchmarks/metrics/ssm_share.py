"""Device time of the operations traced under the program's ``ssm/conv``,
``ssm/scan``, ``ssm/update`` and ``ssm/norm`` scopes (an operation's
``tf_op`` in the trace; ``harness/costs_ssm.py`` ``is_ssm_op``) over device
busy time: what the state-space layers cost beyond their two projections,
which are plain matmuls and are not counted, in prefill chunks and decode
batches alike. None where the program has no such scope."""

from benchmarks.harness import costs_ssm, named_readers
from benchmarks.harness import trace as tr


def read(obs):
    found = named_readers.named_ops(obs)
    if found is None:
        return None
    ops, devices, _ = found
    own = sum(o.dur for o in ops if costs_ssm.is_ssm_op(o))
    busy = tr.busy_seconds(obs.trace, obs.trace.window)
    if not own or not busy:
        return None
    return 100.0 * own / len(devices) / busy
