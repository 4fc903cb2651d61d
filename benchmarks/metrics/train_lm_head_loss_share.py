"""Device time of the operations traced under the trained step's
``lm_head_loss`` scope (``models/llama.py``: the output head and the loss
behind it, forward and backward) over device busy time. None where the
program opens no such scope."""

from benchmarks.harness import scope_readers

SCOPES = ('lm_head_loss',)


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
