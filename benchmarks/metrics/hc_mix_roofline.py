"""The mixing of the residual streams against its memory roofline, from
inside the program: the bytes the algorithm requires (``harness/costs_hc.py``:
the rows that are no padding of every step program, ``tokens`` on
``serve/prefill_chunk`` and ``batch`` on ``serve/step_decode``, x 2 sublayers
x layers x ``(3 n + 2) C`` values) over the published HBM bandwidth, divided
by the device time of the operations under the program's ``hc`` scopes,
whatever implements them. Over the ticks that ran wholly inside the traced
window and ended in a wait for the device
(``costs_latent.ticks_with_device_work``). None where the program has no such
scope or the configuration no streams."""

from benchmarks.harness import (costs_hc, costs_latent, named_readers, peaks,
                                readers)
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr


def read(obs):
    hf = obs.cell.config
    found = named_readers.named_ops(obs)
    if found is None or "hc_mult" not in hf:
        return None
    evs = pe.events()
    intervals = [interval for interval, _ in
                 costs_latent.ticks_with_device_work(evs, obs.trace.window)]
    if not intervals:
        return None
    ops, _, shift = found
    in_a_tick = tr.held_whole(intervals)
    spent = sum(o.dur for o in ops if costs_hc.is_mix_op(o)
                and in_a_tick(o.start + shift, o.end + shift))
    tokens = costs_hc.step_tokens(evs, intervals)
    if not spent or not tokens:
        return None
    least = costs_hc.mix_bytes(tokens, hf, readers.itemsize(hf)) \
        / peaks.peak(obs.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / spent
