"""The paged-attention decode kernel against its memory roofline, from inside
the program: bytes from what the decode ticks decoded (the
``ctx_tokens_windowed`` the program puts on ``serve/step_decode``: K and V of
every token each planned sequence can see; ``harness/costs.py``) over the
published HBM bandwidth, divided by the device time of the calls named
``paged_attention`` in the decode step programs. Over the ticks that ran
wholly inside the traced window; a call counts when it ran inside one of
those ticks' spans."""

from benchmarks.harness import costs, named_readers, peaks, readers
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn


def read(obs):
    found = named_readers.named_ops(obs)
    ticks = [e for e in pe.inside(pe.events(), obs.trace.window)
             if e.name == pe.STEP_DECODE
             and e.arg("ctx_tokens_windowed") is not None] if found else []
    if not ticks:
        return None
    ops, _, shift = found
    hf = obs.cell.config
    need = hf["num_hidden_layers"] * costs.paged_decode_bytes(
        [e.arg("ctx_tokens_windowed") for e in ticks],
        hf["num_key_value_heads"], readers.head_dim(hf), readers.itemsize(hf))
    in_a_tick = tr.held_whole((t.start, t.end) for t in ticks)
    spent = sum(o.dur for o in ops
                if xn.kernel_of(o) == "paged_attention"
                and "decode_step" in o.program
                and in_a_tick(o.start + shift, o.end + shift))
    if not spent:
        return None
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
