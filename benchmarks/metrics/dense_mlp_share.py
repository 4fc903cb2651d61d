"""Device time of the operations traced under the program's ``mlp``,
``moe/shared`` and ``moe/router`` scopes (an operation's ``tf_op``) over
device busy time: the dense MLPs (a leading dense layer's, a shared
expert's) and the routers, which every row passes whatever the router
chooses. None where the program opens no such scope."""

from benchmarks.harness import scope_readers

SCOPES = ('mlp', 'moe/shared', 'moe/router')


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
