"""Device time of the operations traced under the program's ``conv/in_proj``,
``conv/shift`` and ``conv/out_proj`` scopes (an operation's ``tf_op`` in the
trace; ``harness/costs_short_conv.py`` ``is_short_conv_op``) over device busy
time: what the gated short-convolution mixers cost, both projections and both
gates with the tail's shift, in prefill chunks and decode batches alike. None
where the configuration states no such layer or the program has no such
scope."""

from benchmarks.harness import costs_short_conv, named_readers
from benchmarks.harness import trace as tr


def read(obs):
    found = named_readers.named_ops(obs)
    if found is None or not costs_short_conv.has_short_conv(obs.cell.config):
        return None
    ops, devices, _ = found
    own = sum(o.dur for o in ops if costs_short_conv.is_short_conv_op(o))
    busy = tr.busy_seconds(obs.trace, obs.trace.window)
    if not own or not busy:
        return None
    return 100.0 * own / len(devices) / busy
