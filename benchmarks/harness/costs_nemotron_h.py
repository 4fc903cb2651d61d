"""Operations and bytes that an UNGATED routed expert (``mlp_hidden_act:
relu2``: ``W_down relu(W_up u)^2``, two matrices and no gate; Nemotron-H)
needs, from shapes, beside ``costs_latent.py``'s gated ones: what the
algorithm requires, never what an implementation executes, so a share
computed from them cannot pass 100% unless the time leaves work out.

A token-expert pair is two matmuls, ``hidden_size x moe_intermediate_size``
each: 4 x hidden x width operations (a gated expert's three make 6:
``costs_latent.grouped_expert_flops``, which would read an ungated layer 1.5
times too high). An expert that has at least one row is read once, its TWO
matrices. At the published NVIDIA-Nemotron-3-Nano-30B-A3B (2,688 x 1,856,
bfloat16): 19,955,712 operations a pair, 19,955,712 bytes an expert touched;
a decode tick of 128 rows x top-6 over one chip's 64 of the router's 128
experts holds about 384 pairs a layer on about 64 experts: 1.28 GB a layer
at 819 GB/s is 1.56 ms where its operations at 197 TFLOP/s are 0.04 ms, so
the memory bounds it.

The state-space layers' costs are ``costs_ssm.py``'s: the configuration file
states that module's key names (``layer_types``, ``mamba_n_heads``,
``mamba_d_head``, ``mamba_d_state``, ``mamba_d_conv``, ``mamba_n_groups``)
beside their published twins, so the same shapes give the same bytes.
"""

from benchmarks.harness import costs_latent

UNGATED_ACT = "relu2"


def is_ungated(hf: dict) -> bool:
    """Whether the configuration's routed experts are two matrices and no
    gate, the way this module counts them."""
    return hf.get("mlp_hidden_act") == UNGATED_ACT \
        and costs_latent.expert_shape(hf) is not None


def ungated_expert_flops(rows: int, hf: dict) -> int:
    """``rows`` token-expert pairs through ``W_down relu(W_up u)^2``: two
    matmuls."""
    return 4 * int(hf["hidden_size"]) * costs_latent.expert_shape(hf)[1] \
        * int(rows)


def ungated_expert_bytes(touched: int, hf: dict, itemsize: int) -> int:
    """The two matrices of every expert with at least one row."""
    return 2 * int(hf["hidden_size"]) * costs_latent.expert_shape(hf)[1] \
        * itemsize * int(touched)


def ungated_least_seconds(rows: int, touched: int, hf: dict, itemsize: int,
                          flops_per_s: float, bytes_per_s: float) -> float:
    """The larger of operations over the peak and bytes over the bandwidth."""
    return max(ungated_expert_flops(rows, hf) / flops_per_s,
               ungated_expert_bytes(touched, hf, itemsize) / bytes_per_s)
