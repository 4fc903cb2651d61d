"""The decode step of the state-space layers against its memory roofline,
from inside the program: the bytes the decode ticks had to move
(``harness/costs_ssm.py`` ``update_bytes``: every live row's float32 state
and its convolution tail read and written once a layer, ``batch`` rows on
the program's ``serve/step_decode``) over the published HBM bandwidth,
divided by the device time of the operations under the program's
``ssm/conv`` and ``ssm/update`` scopes in the decode step programs (the
tail's gather, shift and scatter and the state's update, ISSUE 44's
definition), whatever implements them (a Pallas kernel, or a gather, an
update and a scatter). Over the ticks that ran wholly inside the
traced window and ended in a wait for the device
(``costs_latent.ticks_with_device_work``; a call counts where it ends,
``costs_latent.ends_in``: with a step in flight no host span holds a step's
kernels). None where the configuration states no state-space layer or the
program has no such scope."""

from benchmarks.harness import (costs_latent, costs_ssm, named_readers, peaks,
                                readers)
from benchmarks.harness import program_events as pe


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or not costs_ssm.has_state(hf):
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    decodes = costs_latent.decode_steps(ticks, "batch")
    if not decodes:
        return None
    ops, _, shift = found
    in_a_tick = costs_latent.ends_in(ticks)
    spent = sum(o.dur for o in ops
                if any(scope in o.scope for scope in costs_ssm.DECODE_SCOPES)
                and "decode_step" in o.program and in_a_tick(o.end + shift))
    if not spent:
        return None
    need = sum(costs_ssm.update_bytes(e.arg("batch"), hf,
                                      readers.itemsize(hf)) for e in decodes)
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
