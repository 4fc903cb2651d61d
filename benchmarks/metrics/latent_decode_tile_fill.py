"""Keys a decode batch's tokens see over the keys the latent decode kernel
multiplies them by, in percent: sum of ``ctx_tokens`` over sum of
``latent_tile_keys`` (what the program puts on a ``serve/step_decode`` span
over a latent pool: each context's live pages rounded out to the kernel's
whole key tiles, from the function that shares the tile with the kernel), on
the program's spans in the window. It says what the tile's width costs: a
context that ends early in its last tile is multiplied against the whole
tile. None where no span carries the count (a program older than PR 53, or a
pool that is not latent) and, like the other shares of the program's spans
(``step_ahead_share``), on a run with no device in its trace."""

from benchmarks.harness import idle_readers
from benchmarks.harness import program_events as pe


def read(obs):
    if not idle_readers.on_a_device(obs):
        return None
    decodes = [e for e in idle_readers.windowed(obs)
               if e.name == pe.STEP_DECODE
               and e.arg("latent_tile_keys") is not None]
    keys = sum(e.arg("latent_tile_keys") for e in decodes)
    if not keys:
        return None
    return 100.0 * sum(e.arg("ctx_tokens") or 0 for e in decodes) / keys
