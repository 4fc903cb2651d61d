"""Device time of the operations traced under the program's ``hc`` scopes
(``hc/pre``, ``hc/post``, ``hc/head``: an operation's ``tf_op`` in the
trace) over device busy time: the mixing of several residual streams round
every sublayer (the norm over the streams, the projection onto the
coefficients, Sinkhorn-Knopp, the weighted sums), in prefill chunks and
decode batches alike. In a decode batch its cost is launches, in a chunk
bytes; lower is better. None where the program has no such scope."""

from benchmarks.harness import costs_hc, named_readers
from benchmarks.harness import trace as tr


def read(obs):
    found = named_readers.named_ops(obs)
    if found is None:
        return None
    ops, devices, _ = found
    mixing = sum(o.dur for o in ops if costs_hc.is_mix_op(o))
    busy = tr.busy_seconds(obs.trace, obs.trace.window)
    if not mixing or not busy:
        return None
    return 100.0 * mixing / len(devices) / busy
