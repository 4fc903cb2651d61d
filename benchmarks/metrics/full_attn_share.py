"""Device time of the operations traced under the program's ``attn/full``
scope (an operation's ``tf_op`` in the trace) over device busy time: the
KV write and the paged attention of the full-attention layers of a cache
that keeps pages by layer kind, in prefill chunks and decode batches alike.
None where the program has no such scope."""

from benchmarks.harness import named_readers
from benchmarks.harness import trace as tr

SCOPE = "/attn/full"


def read(obs):
    found = named_readers.named_ops(obs)
    if found is None:
        return None
    ops, devices, _ = found
    attn = sum(o.dur for o in ops if SCOPE in o.scope)
    busy = tr.busy_seconds(obs.trace, obs.trace.window)
    if not attn or not busy:
        return None
    return 100.0 * attn / len(devices) / busy
