"""Model FLOP/s utilization of the traced steps: operations the forward and
backward passes require per token (attention counted at its window,
recomputation not counted; ``harness/costs.py``) times tokens per second,
over chips times the published bf16 peak."""


from benchmarks.harness import costs, peaks, readers


def read(obs):
    t, hf = obs.train, obs.cell.config
    # no device plane in the trace: not a chip, and no utilization of one
    if not t or obs.trace is None or not obs.trace.devices:
        return None
    per_token = costs.train_flops_per_token(
        hf["num_hidden_layers"], hf["hidden_size"], hf["intermediate_size"],
        hf["num_attention_heads"], hf["num_key_value_heads"],
        readers.head_dim(hf),
        hf["vocab_size"], t["seq_len"], hf.get("sliding_window"),
        hf.get("num_experts_per_tok", 1))
    t0, t1 = obs.window
    rate = t["steps"] * t["tokens_per_step"] / (t1 - t0)
    return 100.0 * per_token * rate / (
        t["chips"] * peaks.peak(obs.device_kind, "bf16_flops_per_s"))
