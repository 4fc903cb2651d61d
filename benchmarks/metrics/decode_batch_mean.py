"""Sequences per decode tick, counted at the call."""

from benchmarks.harness import readers


def read(obs):
    return readers.mean_per_tick(obs, "decode_tokens")
