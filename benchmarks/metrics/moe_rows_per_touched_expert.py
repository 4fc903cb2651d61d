"""Rows an expert that got any took in one expert layer of one step program,
on average: sum of ``expert_rows`` over sum of ``experts_touched`` on the
program's ``serve/prefill_chunk`` and ``serve/step_decode`` spans in the
window (both counted on the device and summed over the expert layers). A
2,048-token chunk over 256 experts top-8 gives 64; a decode batch of 32 gives
1.6. None where the program counts nothing."""

from benchmarks.harness import costs_latent
from benchmarks.harness import program_events as pe


def read(obs):
    evs = pe.inside(pe.events(), obs.window, obs.outside_stall)
    counts = costs_latent.step_counts(evs)
    touched = sum(t for _, t in counts)
    if not touched:
        return None
    return sum(r for r, _ in counts) / touched
