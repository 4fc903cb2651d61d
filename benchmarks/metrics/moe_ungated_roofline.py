"""The UNGATED grouped expert matmuls (``mlp_hidden_act: relu2``, two
matrices an expert) against their roofline, from inside the program: what
``moe_grouped_roofline`` is to a gated expert, with the counts of
``harness/costs_nemotron_h.py``. The least time of a step program is the
larger of its token-expert pairs x 4 x hidden x expert width operations over
the published bf16 peak and its touched experts x TWO matrices' bytes over the
published HBM bandwidth (``expert_rows`` and ``experts_touched`` are counted
on the device, summed over the expert layers, and put on
``serve/prefill_chunk`` and ``serve/step_decode``), over the device time of
the experts' operations (``costs_latent.is_grouped_expert_op``: the sort, the
gathers, the grouped matmuls and the combine, each operation once; the shared
expert runs under ``moe/shared`` and is not among them). Over the ticks that
ran wholly inside the traced window and ended in a wait for the device. None
where the configuration's experts are gated or the program counts nothing."""

from benchmarks.harness import (costs_latent, costs_nemotron_h, named_readers,
                                peaks, readers)
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr


def read(obs):
    hf = obs.cell.config
    if not costs_nemotron_h.is_ungated(hf):
        return None
    found = named_readers.named_ops(obs)
    if found is None:
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    counts = costs_latent.step_counts([e for _, work in ticks for e in work])
    if not counts:
        return None
    ops, _, shift = found
    in_a_tick = tr.held_whole(interval for interval, _ in ticks)
    spent = sum(o.dur for o in ops
                if costs_latent.is_grouped_expert_op(o)
                and in_a_tick(o.start + shift, o.end + shift))
    if not spent:
        return None
    flops = peaks.peak(obs.device_kind, "bf16_flops_per_s")
    bandwidth = peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    least = sum(costs_nemotron_h.ungated_least_seconds(
        rows, touched, hf, readers.itemsize(hf), flops, bandwidth)
        for rows, touched in counts)
    return 100.0 * least / spent
