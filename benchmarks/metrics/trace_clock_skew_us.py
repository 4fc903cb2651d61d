"""How far the two clocks drift over the traced window: the largest
difference between a program span's start on the tracer's ring
(``time.monotonic``) and the same span's start in the profiler's trace, after
the harness's one-mark shift. The program mirrors every live span into the
profiler, so each is stamped on both clocks within microseconds."""

from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn


def read(obs):
    names = xn.of_run(obs)
    if names is None or not names.devices:
        return None
    shift = xn.shift_to_monotonic(names, obs.trace)
    if shift is None:
        return None
    skew = xn.clock_skew_s(names, pe.events(), shift, obs.trace.window)
    return None if skew is None else skew * 1e6
