"""Panels of the flash forward kernel that build and apply a mask over all
its panels that compute, in percent: ``flash_panels_masked`` over
``flash_panels`` of the program's own ``flash_attention.panels`` (one head's
forward grid at the panel ``flash_attention_auto`` chooses, from the
schedule the kernels themselves run) for the cell's ``seq_len`` and the
configuration's ``sliding_window`` and head width. It says how often the
kernels' cheap body, which masks nothing, engages: the rest of the panels
lie wholly inside the causal band. None where the program has no such
function (a program older than PR 50), where the configuration does not
train through the flash kernels and, like the other shares of the program's
counts, on a run with no device in its trace."""

from benchmarks.harness import readers


def read(obs):
    t, hf = obs.train, obs.cell.config
    if not t or obs.trace is None or not obs.trace.devices:
        return None
    if hf.get("train", {}).get("attention_backend") != "flash":
        return None
    try:
        from deepspeed_tpu.ops.pallas.flash_attention import panels
    except ImportError:
        return None
    counts = panels(t["seq_len"], t["seq_len"], True,
                    hf.get("sliding_window"), head_dim=readers.head_dim(hf))
    if not counts["flash_panels"]:
        return None
    return 100.0 * counts["flash_panels_masked"] / counts["flash_panels"]
