"""The latent paged-attention decode kernel against its memory roofline,
from inside the program: bytes from what the decode ticks decoded (the
``ctx_tokens`` the program puts on ``serve/step_decode``, one cached row of
``kv_lora_rank + qk_rope_head_dim`` values a token a layer;
``harness/costs_latent.py``) over the published HBM bandwidth, divided by the
device time of the calls named ``latent_paged_attention`` in the decode step
programs. Over the ticks that ran wholly inside the traced window; a call
counts when it ran inside one of those ticks' spans. None where the program
has no such kernel."""

from benchmarks.harness import costs_latent, named_readers, peaks, readers
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn

KERNEL = "latent_paged_attention"


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or "kv_lora_rank" not in hf:
        return None
    ticks = [e for e in pe.inside(pe.events(), obs.trace.window)
             if e.name == pe.STEP_DECODE and e.arg("ctx_tokens") is not None]
    if not ticks:
        return None
    ops, _, shift = found
    in_a_tick = tr.held_whole((t.start, t.end) for t in ticks)
    spent = sum(o.dur for o in ops
                if xn.kernel_of(o) == KERNEL and "decode_step" in o.program
                and in_a_tick(o.start + shift, o.end + shift))
    if not spent:
        return None
    row = costs_latent.latent_row_bytes(hf, readers.itemsize(hf))
    need = hf["num_hidden_layers"] * sum(
        costs_latent.latent_decode_bytes(e.arg("ctx_tokens"), row)
        for e in ticks)
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
