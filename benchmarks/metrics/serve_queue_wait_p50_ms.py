"""Median wait between a request's arrival at the server and its admission
to the engine: the program's ``serve/queued`` span."""

from benchmarks.harness import readers


def read(obs):
    return readers.span_median_ms(obs, "serve/queued")
