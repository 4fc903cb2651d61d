"""Device time of ``copy`` operations inside the decode step programs over
all device time of those programs. ``generic_decode``'s jits donate nothing,
so XLA copies the whole KV pool inside every step; this is that cost."""

from benchmarks.harness import readers


def read(obs):
    ops = readers.compute_ops(obs, "decode_step")
    whole = sum(o.dur for o in ops)
    if not whole:
        return None
    return 100.0 * sum(o.dur for o in ops if o.kind == "copy") / whole
