"""Share of the traced window in which a collective operation ran on a chip
and no compute operation ran on it, averaged over the chips: the part of the
ZeRO-3 gathers and reduce-scatters that compute does not hide."""


from benchmarks.harness import trace as tr


def read(obs):
    if obs.trace is None or len(obs.trace.devices) < 2:
        return None
    a, b = obs.trace.window
    return 100.0 * tr.exposed_collective_seconds(obs.trace, (a, b)) / (b - a)
