"""The paged-attention kernel in prefill chunks over pages held by layer
kind against the bf16 peak, from inside the program: the operations the
chunks' attention requires (``harness/costs_kind_pages.py``: each row's
visible keys, a windowed layer's at most ``sliding_window``, times the kind's
query heads times 2 x (192 + 128) at the published sizes; the rows from
``start`` and ``tokens`` on the program's ``serve/prefill_chunk`` spans) over
the published peak, divided by the device time of the calls named
``paged_attention`` in the chunk step programs, full and windowed layers'
alike. Over the ticks that ran wholly inside the traced window and ended in
a wait for the device (``costs_latent.ticks_with_device_work``; a call
counts inside the stretch their intervals cover together,
``costs_kind_pages.joined``). None where
the configuration states no layer kinds of their own shapes or the program
has no such kernel."""

from benchmarks.harness import (costs_kind_pages, costs_latent, named_readers,
                                peaks)
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
    chunks = [e for _, work in ticks for e in work
              if e.name == pe.PREFILL_CHUNK and e.arg("tokens") is not None]
    if not chunks:
        return None
    ops, _, shift = found
    in_a_tick = tr.held_whole(costs_kind_pages.joined(
        interval for interval, _ in ticks))
    spent = sum(o.dur for o in ops
                if xn.kernel_of(o) == KERNEL and "prefill_chunk" in o.program
                and in_a_tick(o.start + shift, o.end + shift))
    if not spent:
        return None
    need = sum(costs_kind_pages.kind_prefill_flops(
        e.arg("start", 0), e.arg("tokens"), hf) for e in chunks)
    least = need / peaks.peak(obs.device_kind, "bf16_flops_per_s")
    return 100.0 * least / spent
