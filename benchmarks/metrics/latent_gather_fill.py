"""Keys a chunk's rows can see over the rows its layers' calls gather and
up-project, in percent: sum of ``start`` + ``tokens`` over sum of
``latent_keys_gathered`` on the program's ``serve/prefill_chunk`` spans in
the window (the second: ``latent_attention.prefill_keys`` of the chunk's
context bucket, what ``kv_cache._latent_prefill_attn`` gathers from the pages
whatever the chunk's horizon). The rest is the bucket's padding. None where no
span carries the count (a program older than PR 54, or a pool that is not
latent)."""

from benchmarks.harness import program_events as pe
from benchmarks.harness import scope_readers


def read(obs):
    return scope_readers.counted_fill(
        obs, (pe.PREFILL_CHUNK,), ("start", "tokens"), "latent_keys_gathered")
