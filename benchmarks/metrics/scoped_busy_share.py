"""Device time of the operations whose ``tf_op`` holds ANY name of the
program's registry of scopes (``telemetry/names.py`` ``SERVED_SCOPES`` and
``SERVED_LEAF_SCOPES``) over the device time of ALL the traced window's
operations: 100 less it is the share of the device's work that the program
does not name, whatever it is (sampling's random bits, the feed of the last
tokens, copies between programs, an operation XLA made of pieces of several
scopes and named after none). Over the operations' own sum and not over busy
time, so that it is a share of one whole and cannot pass 100. None for an
untraced run."""

from benchmarks.harness import scope_readers


def read(obs):
    return scope_readers.named_share(obs, scope_readers.registry())
