"""Process start to the first instant of the measured window: imports,
weights, compiling or loading from the cache, the correctness check,
warm-up and, for a served cell, the ramp."""


def read(obs):
    if obs.setup_end is None:
        return None
    return obs.setup_end - obs.process_start
