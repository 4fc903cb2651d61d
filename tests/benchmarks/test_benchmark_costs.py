"""Operations, bytes and peaks: hand-worked cases."""

import json
import pathlib

import pytest

from benchmarks.harness import costs, costs_latent, peaks

REPO = pathlib.Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("seq,window,pairs", [
    (4, None, 10),          # 1 + 2 + 3 + 4
    (4, 2, 7),              # 1 + 2 + 2 + 2
    (4, 8, 10),             # a window longer than the row masks nothing
    (8192, 4096, 4096 * 4097 // 2 + 4096 * 4096),
])
def test_causal_window_pairs(seq, window, pairs):
    assert costs.causal_window_pairs(seq, window) == pairs


def test_flash_flops_by_hand():
    # 1 row, 2 heads, 4 tokens, head size 8, window 2: 7 pairs a head;
    # forward = QK^T + PV = 2 x (2 x 8) FLOPs a pair
    assert costs.flash_fwd_flops(1, 2, 4, 8, 2) == 2 * 7 * 32
    # backward = four matmuls, twice the forward; flash's recomputed QK^T
    # is not counted
    assert costs.flash_bwd_flops(1, 2, 4, 8, 2) == 2 * 7 * 64


def test_paged_decode_bytes_by_hand():
    # contexts 100 and 5000 tokens, window 4096, 8 kv heads x 128, bf16:
    # K and V of 100 + 4096 tokens
    assert costs.paged_decode_bytes([100, 5000], 8, 128, 2, 4096) == \
        2 * (100 + 4096) * 8 * 128 * 2
    assert costs.paged_decode_bytes([100, 5000], 8, 128, 2, None) == \
        2 * 5100 * 8 * 128 * 2


def test_mistral_layer_weights_by_hand():
    # q and o: 4096 x 4096 each; k and v: 4096 x 1024 each; MLP: 3 x 4096 x 14336
    per_layer = costs.dense_layer_matmul_params(4096, 14336, 32, 8, 128)
    assert per_layer == 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    # Mixtral: two experts a token
    assert costs.dense_layer_matmul_params(4096, 14336, 32, 8, 128, 2) == \
        per_layer + 3 * 4096 * 14336


def test_train_flops_per_token_by_hand():
    # 2 layers of Mistral-7B widths at 8192 tokens, window 4096
    weights = 2 * 218_103_808 + 4096 * 32000
    pairs_per_token = (4096 * 4097 // 2 + 4096 * 4096) / 8192
    attention = 2 * 32 * 12 * 128 * pairs_per_token
    got = costs.train_flops_per_token(2, 4096, 14336, 32, 8, 128, 32000,
                                      8192, 4096)
    assert got == pytest.approx(6 * weights + attention)
    assert 3.6e9 < got < 3.8e9


def test_v5e_peaks_and_unknown_devices():
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu", "bf16_flops_per_s")
    with pytest.raises(KeyError):
        peaks.peak("TPU v5 lite", "fp8_flops_per_s")


# --- a routed expert's count and width, under each family's own keys ----------

def _config(name):
    return json.loads((REPO / "benchmarks" / "configs" /
                       f"{name}.json").read_text())


@pytest.mark.parametrize("config,held,width,hidden", [
    # DeepSeek's names: all 256 experts of a layer on the chip
    ("joyai-llm-flash-serve-d5", 256, 768, 2048),
    ("xing4.0-29b-a4b-serve-d7", 64, 1024, 3584),
    # a HELD share counts the experts held: 16 of the router's 256
    ("mimo-v2.5-serve-d7-e16", 16, 2048, 4096),
    # Qwen2-MoE's names: 128 of 256
    ("laguna-s-2.1-serve-d5-e128", 128, 1024, 3072),
])
def test_expert_shape_reads_the_familys_own_keys(config, held, width, hidden):
    hf = _config(config)
    assert costs_latent.expert_shape(hf) == (held, width)
    if "published" in hf and len(hf["reduced"]) > 1:
        count_key = next(k for k in hf["reduced"] if k.endswith("_experts"))
        assert held == hf[count_key] < hf["published"][count_key]
    # a row through an expert is three matmuls, an expert three matrices
    assert costs_latent.grouped_expert_flops(1, hf) == 6 * hidden * width
    assert costs_latent.grouped_expert_bytes(1, hf, 2) == 6 * hidden * width
    # every expert held, touched by one row each: the weights bound it
    assert costs_latent.grouped_least_seconds(
        held, held, hf, 2, 197e12, 819e9) == pytest.approx(
            held * 6 * hidden * width / 819e9)


def test_expert_shape_of_a_dense_model_is_none():
    assert costs_latent.expert_shape(_config("mistral-7b-serve-d8")) is None
    assert costs_latent.expert_shape({"n_routed_experts": None,
                                      "moe_intermediate_size": 64}) is None
