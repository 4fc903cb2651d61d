"""Device time of the operations traced under ``attn/kv_write`` or
``attn/latent_write`` (an operation's ``tf_op``; ``telemetry/names.py``
``SERVED_SCOPES``) over device busy time: a step's rows into the paged pool,
a chunk's a page at a time (the shift into page frames and the kernel
``kv_page_write``) and a decode batch's by XLA's row scatter. A program older
than PR 55 reads the index arithmetic alone here (0.3-1.0): its chunk's
scatter of one row an update was lowered in place and carried NO name, so
the parent's reading is not what its write cost (that was 14-18% of busy
time in the prompt-heavy cells, under no scope: PERF.md section 7, PR 54).
None for an untraced run and where the program opens neither scope."""

from benchmarks.harness import scope_readers

SCOPES = ("attn/kv_write", "attn/latent_write")


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
