"""Device time of the operations traced under ``attn/latent_prefill/up_proj``
(an operation's ``tf_op``; ``telemetry/names.py`` ``SERVED_LEAF_SCOPES``)
over device busy time: the two ``einsum``s that make every head's keys and
values of a chunk's whole context bucket from the gathered compressed rows,
a chunk at a time, whatever an earlier chunk of the same prompt made. None
where the program opens no such leaf (one older than PR 54, or a pool that is
not latent)."""

from benchmarks.harness import scope_readers

SCOPES = ('attn/latent_prefill/up_proj',)


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
