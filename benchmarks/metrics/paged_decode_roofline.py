"""The paged-attention decode kernel against its memory roofline: the bytes
its calls had to read (K and V of every token each decoded sequence can see,
from shapes; ``harness/costs.py``) over the chip's published HBM bandwidth,
divided by the kernel's device time. Decode attention at these batch sizes is
bound by bytes, not operations. Taken over the ticks that ran wholly inside
the traced sub-window."""

from benchmarks.harness import costs, peaks, readers
from benchmarks.harness import trace as tr


def read(obs):
    hf = obs.cell.config
    ticks = [t for t in readers.traced_ticks(obs) if t[3]]
    if not ticks:
        return None
    need = hf["num_hidden_layers"] * sum(costs.paged_decode_bytes(
        t[3], hf["num_key_value_heads"], readers.head_dim(hf),
        readers.itemsize(hf), hf.get("sliding_window")) for t in ticks)
    in_a_tick = tr.held_whole((t[0], t[1]) for t in ticks)
    spent = sum(o.dur for o in readers.kernels(
        readers.compute_ops(obs, "decode_step")) if in_a_tick(o.start, o.end))
    if not spent:
        return None
    least = need / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
