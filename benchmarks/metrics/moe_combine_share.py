"""Device time of the operations traced under ``moe/experts/combine`` (an
operation's ``tf_op``; ``telemetry/names.py`` ``SERVED_LEAF_SCOPES``) over
device busy time: what the routed experts' wrapper spends behind the grouped
matmuls, zeroing the rows past the groups, gathering the ``[T*K, D]`` rows
back into token order and summing each token's top-k with its weights. None
where the program opens no such leaf (one older than PR 54)."""

from benchmarks.harness import scope_readers

SCOPES = ('moe/experts/combine',)


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
