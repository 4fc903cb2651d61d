"""Device time of the operations traced under the program's ``attn/latent``
scopes (``attn/latent_q``, ``attn/latent_write``, ``attn/latent_paged``,
``attn/latent_prefill``: an operation's ``tf_op`` in the trace) over device
busy time: a latent cache's attention from the low-rank projections to the
value up-projection, kernels included, in prefill and decode alike. None
where the program has no such scope."""

from benchmarks.harness import named_readers
from benchmarks.harness import trace as tr

SCOPE = "/attn/latent"


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
