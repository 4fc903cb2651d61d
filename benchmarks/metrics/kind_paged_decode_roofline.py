"""The paged-attention decode kernel over pages held by layer kind, each
kind with its own KV heads and key and value widths, against its memory
roofline, from inside the program: the bytes the decode ticks had to read
(``harness/costs_kind_pages.py``: the full layers' K and V of ``ctx_tokens``,
the windowed layers' of ``ctx_tokens_windowed``, both on the program's
``serve/step_decode``, at each kind's own heads and widths and no row's
padding) over the published HBM bandwidth, divided by the device time of the
calls named ``paged_attention`` in the decode step programs, full and
windowed layers' alike. Over the ticks that ran wholly inside the traced
window and ended in a wait for the device
(``costs_latent.ticks_with_device_work``: with a step in flight a tick's
programs run on the device after its own span has ended, so a call counts
when it ran inside the stretch those ticks' intervals cover together,
``costs_kind_pages.joined``: a program's first call lies across the boundary
between two of them). A chunk's attention is bound by its operations, not by these
bytes, and is left out (``kind_paged_prefill_roofline``). None where the
configuration states no layer kinds of their own shapes or the program has no
such kernel."""

from benchmarks.harness import (costs_kind_pages, costs_latent, named_readers,
                                peaks, readers)
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn

KERNEL = "paged_attention"


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or not costs_kind_pages.has_kinds(hf):
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    decodes = [e for _, work in ticks for e in work
               if e.name == pe.STEP_DECODE and e.arg("ctx_tokens") is not None
               and e.arg("ctx_tokens_windowed") is not None]
    if not decodes:
        return None
    ops, _, shift = found
    in_a_tick = tr.held_whole(costs_kind_pages.joined(
        interval for interval, _ in ticks))
    spent = sum(o.dur for o in ops
                if xn.kernel_of(o) == KERNEL and "decode_step" in o.program
                and in_a_tick(o.start + shift, o.end + shift))
    if not spent:
        return None
    need = sum(costs_kind_pages.kind_decode_bytes(
        e.arg("ctx_tokens"), e.arg("ctx_tokens_windowed"), hf,
        readers.itemsize(hf)) for e in decodes)
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
