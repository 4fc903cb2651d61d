"""The decode step of the short-convolution layers against its memory
roofline, from inside the program: the bytes the decode ticks had to move
(``harness/costs_short_conv.py`` ``shift_bytes``: every live row's tail read
and written once a layer, its token's row in and its convolved row out,
``batch`` rows on the program's ``serve/step_decode``) over the published HBM
bandwidth, divided by the device time of the operations under the program's
``conv/shift`` scope in the decode step programs, whatever implements the
shift (a Pallas kernel addressed by the rows' slots, or a gather, a shift and
a scatter). Over the ticks that ran wholly inside the traced window and ended
in a wait for the device (``costs_latent.ticks_with_device_work``; a call
counts where it ends, ``costs_latent.ends_in``: with a step in flight no host
span holds a step's kernels). None where the configuration states no such
layer or the program has no such scope."""

from benchmarks.harness import (costs_latent, costs_short_conv, named_readers,
                                peaks, readers)
from benchmarks.harness import program_events as pe


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or not costs_short_conv.has_short_conv(hf):
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    decodes = costs_latent.decode_steps(ticks, "batch")
    if not decodes:
        return None
    ops, _, shift = found
    in_a_tick = costs_latent.ends_in(ticks)
    spent = sum(o.dur for o in ops
                if costs_short_conv.SHIFT_SCOPE in o.scope
                and "decode_step" in o.program and in_a_tick(o.end + shift))
    if not spent:
        return None
    need = sum(costs_short_conv.shift_bytes(e.arg("batch"), hf,
                                            readers.itemsize(hf))
               for e in decodes)
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
