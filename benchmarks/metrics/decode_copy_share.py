"""Device time of ``copy`` operations inside the decode step programs over
all device time of those programs. Until PR 28 two pool-sized copies a step
(changes of layout around the KV scatter) took 33-39% of it. Since then the
step programs are given the pool to consume and update it in place, and this
reads 0.04%: it stays as the guard that a copy of the pool has not come
back."""

from benchmarks.harness import readers


def read(obs):
    ops = readers.compute_ops(obs, "decode_step")
    whole = sum(o.dur for o in ops)
    if not whole:
        return None
    return 100.0 * sum(o.dur for o in ops if o.kind == "copy") / whole
