"""Operations, bytes and peaks: hand-worked cases."""

import pytest

from benchmarks.harness import costs, peaks


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
