"""Median host time of one decode tick (build the batch, dispatch, wait for
the sampled tokens): the program's ``serve/step_decode`` span."""

from benchmarks.harness import readers


def read(obs):
    return readers.span_median_ms(obs, "serve/step_decode")
