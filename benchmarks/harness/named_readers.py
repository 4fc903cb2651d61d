"""Readers that several metrics (``metrics/<metric>.py``) share and that take
what the program names: the phases of its tick (``program_events``) and its
kernels' names in the device trace (``xplane_names``). Each returns None
where it finds nothing to read, e.g. on a program that has no such span or
names no kernel."""

from benchmarks.harness import peaks, program_events as pe, readers
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn


def idle_attributed_share(obs):
    """Seconds of the device's idle gaps in the traced window whose midpoint
    lies in a leaf phase span of the serve loop (``serve/plan``,
    ``serve/decode_*``, ``serve/prefill_chunk``, ``serve/admit``,
    ``serve/bookkeep``, the drain parts, ...) over all idle seconds, in
    percent. A gap whose shortest span is ``serve/step_decode``,
    ``serve/step_prefill``, ``serve/engine_step``, ``serve/tick`` or none is
    not attributed."""
    if obs.trace is None or not obs.trace.devices:
        return None
    gaps = tr.idle_gaps(obs.trace, obs.trace.window, obs.trace.devices[0])
    return pe.attributed_idle_share(gaps, pe.loop_thread(pe.events()))


def named_ops(obs):
    """(operations of the traced window with their kernel names and scopes,
    devices, seconds to add to an operation's time to reach the monotonic
    clock) from the run's ``.xplane.pb``, or None."""
    names = xn.of_run(obs)
    if names is None or not names.devices:
        return None
    shift = xn.shift_to_monotonic(names, obs.trace)
    if shift is None:
        return None
    return (xn.in_window(names.ops, obs.trace.window, shift), names.devices,
            shift)


def flash_roofline_share(obs, kernels, flops, passes=1):
    """The operations the traced steps' attention needs in one direction
    (``flops``: a function of ``costs`` taking rows, heads, seq_len, head_dim,
    window; ``passes``: how often each layer runs it a step) over the
    published bf16 peak, divided by the device time of the Pallas calls named
    in ``kernels``. On several chips each runs its own rows and the calls'
    time is summed over the chips, so the share is per chip."""
    found = named_ops(obs)
    t, hf = obs.train, obs.cell.config
    if found is None or not t:
        return None
    spent = sum(o.dur for o in found[0] if xn.kernel_of(o) in kernels)
    if not spent:
        return None
    rows = t["tokens_per_step"] // t["seq_len"] * t["steps"]
    need = hf["num_hidden_layers"] * passes * flops(
        rows, hf["num_attention_heads"], t["seq_len"], readers.head_dim(hf),
        hf.get("sliding_window"))
    least = need / peaks.peak(obs.device_kind, "bf16_flops_per_s")
    return 100.0 * least / spent
