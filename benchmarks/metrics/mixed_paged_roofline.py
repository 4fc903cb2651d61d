"""The paged-attention decode kernel over pages held by layer kind against
its memory roofline, from inside the program: the bytes the decode ticks had
to read (``harness/costs_mixed_pages.py``: the full layers' K and V of
``ctx_tokens``, the windowed layers' of ``ctx_tokens_windowed``, both on the
program's ``serve/step_decode``) over the published HBM bandwidth, divided by
the device time of the calls named ``paged_attention`` in the decode step
programs, full and windowed layers' alike. Bytes and calls come from the same
stretches: the ticks that ended in a wait for the device and ran wholly
inside the traced window (``costs_latent.ticks_with_device_work``), and the
calls that ended inside them (``costs_latent.ends_in``): with a step in
flight no host span holds a step's calls. A chunk's attention is
bound by its operations, not by these bytes, and is left out. None where the
configuration has no ``layer_types`` or the program no such kernel."""

from benchmarks.harness import (costs_latent, costs_mixed_pages,
                                named_readers, peaks, readers)
from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn

KERNEL = "paged_attention"


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or "layer_types" not in hf:
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    decodes = costs_latent.decode_steps(ticks, "ctx_tokens",
                                        "ctx_tokens_windowed")
    if not decodes:
        return None
    ops, _, shift = found
    in_a_tick = costs_latent.ends_in(ticks)
    spent = sum(o.dur for o in ops
                if xn.kernel_of(o) == KERNEL and "decode_step" in o.program
                and in_a_tick(o.end + shift))
    if not spent:
        return None
    need = sum(costs_mixed_pages.mixed_decode_bytes(
        e.arg("ctx_tokens"), e.arg("ctx_tokens_windowed"), hf,
        readers.itemsize(hf)) for e in decodes)
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
