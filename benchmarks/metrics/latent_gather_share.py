"""Device time of the operations traced under ``attn/latent_prefill/gather``
(an operation's ``tf_op``; ``telemetry/names.py`` ``SERVED_LEAF_SCOPES``)
over device busy time: a chunk's gather of its context's rows from the latent
pool's pages, ``pool[layer, table]`` over the whole context bucket, with the
cuts to the rows' compressed and rope parts. None where the program opens no
such leaf (one older than PR 54, or a pool that is not latent)."""

from benchmarks.harness import scope_readers

SCOPES = ('attn/latent_prefill/gather',)


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
