"""The grouped expert matmuls against their roofline, from inside the
program. The least time of a step program is the larger of its token-expert
pairs x 6 x hidden x expert width (under the family's own key:
``costs_latent.expert_shape``) operations over the published bf16 peak and
its touched experts x three matrices' bytes over the published HBM bandwidth
(``harness/costs_latent.py``; ``expert_rows`` and ``experts_touched`` are
counted on the device, summed over the expert layers, and put on
``serve/prefill_chunk`` and ``serve/step_decode``), over the device time
of the experts' operations (``costs_latent.is_grouped_expert_op``: the sort,
the gathers, the grouped matmuls and the combine, each operation once). Over the ticks that ran wholly inside the traced window and
ended in a wait for the device. None where the program counts nothing."""

from benchmarks.harness import costs_latent, named_readers, peaks, readers
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or costs_latent.expert_shape(hf) is None:
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
    least = sum(costs_latent.grouped_least_seconds(
        rows, touched, hf, readers.itemsize(hf), flops, bandwidth)
        for rows, touched in counts)
    return 100.0 * least / spent
