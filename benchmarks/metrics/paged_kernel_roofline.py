"""The paged-attention decode kernel against its memory roofline, from inside
the program: bytes from what the decode ticks decoded (the
``ctx_tokens_windowed`` the program puts on ``serve/step_decode``: K and V of
every token each planned sequence can see; ``harness/costs.py``) over the
published HBM bandwidth, divided by the device time of the calls named
``paged_attention`` in the decode step programs. Bytes and calls come from
the same stretches: the ticks that ended in a wait for the device and ran
wholly inside the traced window, from the end of one ``serve/decode_wait`` to
the end of the next (``costs_latent.ticks_with_device_work``), and the calls
that ended inside them (``costs_latent.ends_in``): with a step in flight no
host span holds a step's calls."""

from benchmarks.harness import (costs, costs_latent, named_readers, peaks,
                                readers)
from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn


def read(obs):
    found = named_readers.named_ops(obs)
    if found is None:
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    decodes = costs_latent.decode_steps(ticks, "ctx_tokens_windowed")
    if not decodes:
        return None
    ops, _, shift = found
    hf = obs.cell.config
    need = hf["num_hidden_layers"] * costs.paged_decode_bytes(
        [e.arg("ctx_tokens_windowed") for e in decodes],
        hf["num_key_value_heads"], readers.head_dim(hf), readers.itemsize(hf))
    in_a_tick = costs_latent.ends_in(ticks)
    spent = sum(o.dur for o in ops
                if xn.kernel_of(o) == "paged_attention"
                and "decode_step" in o.program and in_a_tick(o.end + shift))
    if not spent:
        return None
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
