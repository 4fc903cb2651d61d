"""The flash-attention backward kernels (the calls named ``flash_bwd_dq`` and
``flash_bwd_dkv``) against their compute roofline: the four backward matmuls
per kept pair (``costs.flash_bwd_flops``; the QK^T both kernels recompute is
not counted) over the two kernels' device time."""

from benchmarks.harness import costs, named_readers


def read(obs):
    return named_readers.flash_roofline_share(
        obs, ("flash_bwd_dq", "flash_bwd_dkv"), costs.flash_bwd_flops)
