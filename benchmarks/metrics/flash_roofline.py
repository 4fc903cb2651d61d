"""The flash-attention kernels against their compute roofline: operations of
the windowed-causal forward and backward (from shapes; ``harness/costs.py``)
over the chip's published bf16 peak, divided by the kernels' device time.
With recomputation on, the forward kernel runs twice a layer and its
operations are counted twice: this is the kernels' achieved rate, not the
model's (that is ``train_mfu``). On several chips each runs its own rows and
the kernels' time is summed over the chips, so the share is per chip."""

from benchmarks.harness import costs, peaks, readers


def read(obs):
    calls = readers.kernels(readers.compute_ops(obs))
    t, hf = obs.train, obs.cell.config
    if not calls or not t:
        return None
    rows = t["tokens_per_step"] // t["seq_len"] * t["steps"]
    shape = (rows, hf["num_attention_heads"], t["seq_len"],
             readers.head_dim(hf), hf.get("sliding_window"))
    forward = costs.flash_fwd_flops(*shape) * (2 if t["remat"] else 1)
    need = hf["num_hidden_layers"] * (forward + costs.flash_bwd_flops(*shape))
    least = need / peaks.peak(obs.device_kind, "bf16_flops_per_s")
    return 100.0 * least / sum(o.dur for o in calls)
