"""Real (unpadded) prompt tokens per tick that prefilled anything."""

from benchmarks.harness import readers


def read(obs):
    return readers.mean_per_tick(obs, "prefill_tokens")
