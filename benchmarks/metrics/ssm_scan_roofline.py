"""The prefill chunks' recurrence in the state-space layers against its
roofline, from inside the program: the least time the chunks' real tokens
need (``harness/costs_ssm.py`` ``scan_least_seconds``: 5 operations a state
element a token over the published bf16 peak, or the rows and each chunk's
state in and out over the HBM bandwidth, whichever is longer; ``tokens`` on
the program's ``serve/prefill_chunk``), divided by the device time of the
operations under the program's ``ssm/scan`` scope in the chunk programs,
whatever implements them. Over the ticks that ran wholly inside the traced
window and ended in a wait for the device
(``costs_latent.ticks_with_device_work``; an operation counts when it ran
inside the stretch those ticks' intervals cover together,
``costs_kind_pages.joined``). The program's closed form over blocks of 256
does several times the recurrence's own operations (a block's [256, 256]
scores a head) in plain ``jax.numpy``, so this reads low: what a kernel for
it would be sized against. None where the configuration states no
state-space layer or the program has no such scope."""

from benchmarks.harness import (costs_kind_pages, costs_latent, costs_ssm,
                                named_readers, peaks, readers)
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or not costs_ssm.has_state(hf):
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    chunks = [e for _, work in ticks for e in work
              if e.name == pe.PREFILL_CHUNK and e.arg("tokens") is not None]
    if not chunks:
        return None
    ops, _, shift = found
    in_a_tick = tr.held_whole(costs_kind_pages.joined(
        interval for interval, _ in ticks))
    spent = sum(o.dur for o in ops if costs_ssm.SCAN_SCOPE in o.scope
                and "prefill_chunk" in o.program
                and in_a_tick(o.start + shift, o.end + shift))
    if not spent:
        return None
    least = costs_ssm.scan_least_seconds(
        len(chunks), sum(e.arg("tokens") for e in chunks), hf,
        readers.itemsize(hf),
        peaks.peak(obs.device_kind, "bf16_flops_per_s"),
        peaks.peak(obs.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least / spent
