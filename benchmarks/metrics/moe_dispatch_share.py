"""Device time of the operations traced under ``moe/experts/sort`` and
``moe/experts/gather`` (an operation's ``tf_op``; ``telemetry/names.py``
``SERVED_LEAF_SCOPES``) over device busy time: what the routed experts'
wrapper spends before the grouped matmuls, sorting the step's assignments by
expert and gathering the ``[T*K, D]`` rows into that order, in chunk programs
and decode batches alike. A cap on the gathered rows, or rows read through
the sorted index inside the kernel, shortens this. None where the program
opens no such leaf (one older than PR 54)."""

from benchmarks.harness import scope_readers

SCOPES = ('moe/experts/sort', 'moe/experts/gather')


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
