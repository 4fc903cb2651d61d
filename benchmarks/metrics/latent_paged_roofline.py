"""The latent paged-attention decode kernel against its memory roofline,
from inside the program: bytes from what the decode ticks decoded (the
``ctx_tokens`` the program puts on ``serve/step_decode``, one cached row of
``kv_lora_rank + qk_rope_head_dim`` values a token a layer;
``harness/costs_latent.py``) over the published HBM bandwidth, divided by the
device time of the calls named ``latent_paged_attention`` in the decode step
programs. Bytes and calls come from the same stretches: the ticks that ended
in a wait for the device and ran wholly inside the traced window
(``costs_latent.ticks_with_device_work``), and the calls that ended inside
them (``costs_latent.ends_in``): with a step in flight no host span holds a
step's calls. None where the program has no such kernel."""

from benchmarks.harness import costs_latent, named_readers, peaks, readers
from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn

KERNEL = "latent_paged_attention"


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or "kv_lora_rank" not in hf:
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    decodes = costs_latent.decode_steps(ticks, "ctx_tokens")
    if not decodes:
        return None
    ops, _, shift = found
    in_a_tick = costs_latent.ends_in(ticks)
    spent = sum(o.dur for o in ops
                if xn.kernel_of(o) == KERNEL and "decode_step" in o.program
                and in_a_tick(o.end + shift))
    if not spent:
        return None
    row = costs_latent.latent_row_bytes(hf, readers.itemsize(hf))
    need = hf["num_hidden_layers"] * sum(
        costs_latent.latent_decode_bytes(e.arg("ctx_tokens"), row)
        for e in decodes)
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
