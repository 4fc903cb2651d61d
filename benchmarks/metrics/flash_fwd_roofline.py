"""The flash-attention forward kernel (the calls named ``flash_fwd``) against
its compute roofline. With recomputation on it runs twice a layer and its
operations are counted twice: the kernel's achieved rate, not the model's."""

from benchmarks.harness import costs, named_readers


def read(obs):
    passes = 2 if obs.train and obs.train["remat"] else 1
    return named_readers.flash_roofline_share(
        obs, ("flash_fwd",), costs.flash_fwd_flops, passes)
