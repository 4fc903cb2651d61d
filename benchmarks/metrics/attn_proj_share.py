"""Device time of the operations traced under the program's ``attn/qkv``,
``attn/out``, ``attn/gate``, ``attn/qk_norm`` and ``attn/latent_q`` scopes
(an operation's ``tf_op``) over device busy time: an attention layer's
projections round its pages, norms and rope with them (a latent cache's
low-rank projections and the decode fold among them), and nothing of the
attention over the pages itself. None where the program opens no such
scope."""

from benchmarks.harness import scope_readers

SCOPES = ('attn/qkv', 'attn/out', 'attn/gate', 'attn/qk_norm', 'attn/latent_q')


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
