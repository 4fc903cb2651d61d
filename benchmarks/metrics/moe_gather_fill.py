"""Rows the grouped matmuls visit over the rows the experts' wrapper sorts,
gathers and gathers back, in percent: sum of ``expert_rows`` over sum of
``expert_rows_gathered`` on the program's ``serve/prefill_chunk`` and
``serve/step_decode`` spans in the window that carry both (the first counted
on the device, the second the step programs' ``ids.size``, rows x top-k,
summed over the expert layers: ``telemetry/names.py``). Bucket padding, an
idle decode half and, most of all, the assignments to experts held on another
chip are gathered and not visited: a chip that holds 16 of 256 experts reads
about 6. None where the program stamps no such count (one older than PR 54,
or one without routed experts)."""

from benchmarks.harness import costs_latent, scope_readers


def read(obs):
    return scope_readers.counted_fill(
        obs, costs_latent.COUNTED_SPANS, (costs_latent.ROWS,),
        "expert_rows_gathered")
