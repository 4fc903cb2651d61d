"""Device time of the operations traced under the program's ``lm_head`` and
``sample`` scopes (an operation's ``tf_op``) over device busy time: the final
norm, the output head over the rows that sample and the sampling itself. A
configuration cut to a few layers of a deep model keeps the whole head, so
its share is larger here than in the model as published. None where the
program opens no such scope."""

from benchmarks.harness import scope_readers

SCOPES = ('lm_head', 'sample')


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
