"""``benchmarks/harness/costs_nemotron_h.py``: the counts from shapes, at the
published NVIDIA-Nemotron-3-Nano-30B-A3B widths, and ``costs_ssm.py``'s over
the key names the configuration file states beside their published twins."""

import json

import pytest

import benchmark_rehearsal as rehearsal
from benchmarks.harness import costs_latent, costs_nemotron_h, costs_ssm

HF = json.loads((rehearsal.REPO / "benchmarks" / "configs" /
                 "nemotron-3-nano-serve-d13-e64.json").read_text())


def test_an_ungated_expert_is_two_matrices():
    assert costs_nemotron_h.is_ungated(HF)
    assert costs_latent.expert_shape(HF) == (64, 1856)
    assert costs_nemotron_h.ungated_expert_flops(1, HF) \
        == 4 * 2688 * 1856 == 19_955_712
    assert costs_nemotron_h.ungated_expert_bytes(1, HF, 2) == 19_955_712
    # two thirds of what a gated expert of the same widths would count
    assert 3 * costs_nemotron_h.ungated_expert_flops(768, HF) \
        == 2 * costs_latent.grouped_expert_flops(768, HF)
    assert 3 * costs_nemotron_h.ungated_expert_bytes(64, HF, 2) \
        == 2 * costs_latent.grouped_expert_bytes(64, HF, 2)
    assert not costs_nemotron_h.is_ungated({**HF, "mlp_hidden_act": "silu"})
    assert not costs_nemotron_h.is_ungated({"mlp_hidden_act": "relu2"})


@pytest.mark.parametrize("rows,touched,bound", [
    (768, 64, "bytes"),          # a decode tick of 128 rows, one layer
    (6144, 64, "bytes"),         # a 1,024-token chunk, one layer
    (49152, 64, "flops")])       # 8,192 tokens: 768 rows an expert
def test_the_memory_bounds_a_tick_and_the_peak_a_long_prefill(rows, touched,
                                                              bound):
    flops = costs_nemotron_h.ungated_expert_flops(rows, HF) / 197e12
    nbytes = costs_nemotron_h.ungated_expert_bytes(touched, HF, 2) / 819e9
    assert costs_nemotron_h.ungated_least_seconds(
        rows, touched, HF, 2, 197e12, 819e9) == max(flops, nbytes)
    assert (nbytes > flops) == (bound == "bytes")


def test_the_state_layers_costs_are_read_from_the_derived_keys():
    assert costs_ssm.has_state(HF) and costs_ssm.state_layers(HF) == 6
    assert costs_ssm.inner_width(HF) == 4096
    assert costs_ssm.conv_channels(HF) == 4096 + 2 * 8 * 128 == 6144
    assert costs_ssm.layer_state_bytes(HF) == 64 * 64 * 128 * 4
    assert costs_ssm.layer_tail_bytes(HF, 2) == 3 * 6144 * 2
    assert costs_ssm.sequence_state_bytes(HF, 2) == 6 * 2_134_016
    assert costs_ssm.page_token_bytes(HF, 2) == 2 * 2 * 2 * 128 * 2 == 2048
    # a decode tick of 128 rows: 3.28 GB of state in and out
    assert costs_ssm.update_bytes(128, HF, 2) == 128 * 2 * 6 * 2_134_016
    assert costs_ssm.scan_flops(1024, HF) == 1024 * 6 * 5 * 4096 * 128
