"""Device time of the routed experts' operations over device busy time, by
the selection ``moe_grouped_roofline`` divides by
(``costs_latent.is_grouped_expert_op``): what ``moe_expert_share`` is to a
program whose expert matmuls carry the ``moe/experts`` scope, for one whose
grouped-matmul calls do not. None where the trace holds no such operation."""

from benchmarks.harness import costs_latent, named_readers
from benchmarks.harness import trace as tr


def read(obs):
    found = named_readers.named_ops(obs)
    if found is None:
        return None
    ops, devices, _ = found
    expert = sum(o.dur for o in ops if costs_latent.is_grouped_expert_op(o))
    busy = tr.busy_seconds(obs.trace, obs.trace.window)
    if not expert or not busy:
        return None
    return 100.0 * expert / len(devices) / busy
