"""Prefill chunks per tick that prefilled anything: how often the step's
token budget cut a prompt (test data: ``benchmark_rehearsal.py`` drops this
reader in beside the toy cell whose configuration sets the budget)."""

from benchmarks.harness import readers


def read(obs):
    return readers.mean_per_tick(obs, "chunks")
