"""Device time of the operations traced under the program's ``ssm/conv``
scope in the decode step programs (an operation's ``tf_op`` in the trace)
over device busy time: what a decode tick spends shifting the convolution
tails of its rows, 1.2% of the bytes a state layer's decode step has to move
(``harness/costs_ssm.py`` ``layer_tail_bytes`` beside ``layer_state_bytes``),
whatever implements the shift (a Pallas kernel addressed by the rows' slots,
or XLA's gather, shift and scatter). The part of ``ssm_share``, and of the
time ``ssm_update_roofline`` divides by, that the state's update is not. None
where the configuration states no state-space layer or the program has no
such scope."""

from benchmarks.harness import costs_ssm, named_readers
from benchmarks.harness import trace as tr

CONV_SCOPE = "/ssm/conv"


def read(obs):
    found = named_readers.named_ops(obs)
    if found is None or not costs_ssm.has_state(obs.cell.config):
        return None
    ops, devices, _ = found
    own = sum(o.dur for o in ops
              if CONV_SCOPE in o.scope and "decode_step" in o.program)
    busy = tr.busy_seconds(obs.trace, obs.trace.window)
    if not own or not busy:
        return None
    return 100.0 * own / len(devices) / busy
