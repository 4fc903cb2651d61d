"""The latent prefill attention kernel against the bf16 peak, from inside the
program: 2 x (192 + 128) operations a kept (query, key) pair a head a layer
at the published sizes (``harness/costs_latent.py``; the pairs from ``start``
and ``tokens`` on the program's ``serve/prefill_chunk`` spans), over the
published peak, divided by the device time of the calls named
``latent_prefill_attention`` under the ``attn/latent_prefill`` scope. Over
the ticks that ran wholly inside the traced window and ended in a wait for
the device (``costs_latent.ticks_with_device_work``). None where the program
has no such kernel."""

from benchmarks.harness import costs_latent, named_readers, peaks
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn

KERNEL = "latent_prefill_attention"
SCOPE = "/attn/latent_prefill"


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or "kv_lora_rank" not in hf:
        return None
    ticks = costs_latent.ticks_with_device_work(pe.events(), obs.trace.window)
    chunks = [e for _, work in ticks for e in work
              if e.name == pe.PREFILL_CHUNK and e.arg("tokens") is not None]
    if not chunks:
        return None
    ops, _, shift = found
    in_a_tick = tr.held_whole(interval for interval, _ in ticks)
    spent = sum(o.dur for o in ops
                if xn.kernel_of(o) == KERNEL and SCOPE in o.scope
                and in_a_tick(o.start + shift, o.end + shift))
    if not spent:
        return None
    pairs = sum(costs_latent.chunk_pairs(e.arg("start", 0), e.arg("tokens"))
                for e in chunks)
    need = hf["num_hidden_layers"] * costs_latent.latent_prefill_flops(pairs, hf)
    least = need / peaks.peak(obs.device_kind, "bf16_flops_per_s")
    return 100.0 * least / spent
