"""The paged-attention decode kernel against its memory roofline: the bytes
its calls had to read (K and V of every token each decoded sequence can see,
from shapes and the contexts the benchmark noted before each engine tick;
``harness/costs.py``) over the chip's published HBM bandwidth, divided by the
device time of the Mosaic calls in the decode step programs. Decode attention
at these batch sizes is bound by bytes, not operations. Bytes and calls come
from the same stretches: the program's ticks that ended in a wait for the
device and ran wholly inside the traced sub-window
(``costs_latent.ticks_with_device_work``), each with the contexts of the
benchmark's own tick round ``engine.step`` that holds its decode span, and
the calls that ended inside them (``costs_latent.ends_in``): with a step in
flight the benchmark's tick holds a part of a step's calls."""

import bisect

from benchmarks.harness import costs, costs_latent, peaks, readers
from benchmarks.harness import program_events as pe


def read(obs):
    hf = obs.cell.config
    watched = sorted(readers.traced_ticks(obs), key=lambda t: t[0])
    if not watched:
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    starts = [t[0] for t in watched]
    contexts = []
    for e in costs_latent.decode_steps(ticks):
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= watched[i][1] and watched[i][3]:
            contexts.append(watched[i][3])
    if not contexts:
        return None
    need = hf["num_hidden_layers"] * sum(costs.paged_decode_bytes(
        c, hf["num_key_value_heads"], readers.head_dim(hf),
        readers.itemsize(hf), hf.get("sliding_window")) for c in contexts)
    in_a_tick = costs_latent.ends_in(ticks)
    spent = sum(o.dur for o in readers.kernels(
        readers.compute_ops(obs, "decode_step")) if in_a_tick(o.end))
    if not spent:
        return None
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
