"""Median host time of one decode tick: the program's ``serve/step_decode``
span, from the start of the batch's build to the end of the commit. Since
PR 38 one step is in flight while the loop runs, so the span of loop k holds
the build and the dispatch of step k and then the wait for the sampled
tokens of step k-1, the step dispatched a loop EARLIER, and their commit:
still the tick less the server's work round the step, but the tokens it
waits for are the step before's."""

from benchmarks.harness import readers


def read(obs):
    return readers.span_median_ms(obs, "serve/step_decode")
