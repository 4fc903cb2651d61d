"""Device time of the operations traced under the trained step's ``optimizer``
scope (``runtime/engine.py``: the update of the parameters and the
optimizer's state) over device busy time. None where the program opens no
such scope."""

from benchmarks.harness import scope_readers

SCOPES = ('optimizer',)


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
