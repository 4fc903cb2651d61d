"""Tokens of the optimizer steps completed in the window, over seconds times
chips. The clock starts after a step that was waited for and stops after
waiting for the last step, with no wait in between."""


def read(obs):
    t = obs.train
    if not t:
        return None
    t0, t1 = obs.window
    return t["steps"] * t["tokens_per_step"] / (t1 - t0) / t["chips"]
