"""Bytes and operations of a served model's state-space layers
(``harness/costs_ssm.py``): hand-worked cases at granite-4.0-h-micro's
published sizes, ISSUE 44's numbers. Beside ``test_benchmark_costs.py``, which
a PR that is no ``benchmark`` PR does not edit."""

import json
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]


def _granite() -> dict:
    return json.loads((REPO / "benchmarks" / "configs" /
                       "granite-4.0-h-micro-serve.json").read_text())


@pytest.mark.parametrize("what,args,want", [
    ("state_layers", (), 36),
    ("inner_width", (), 4096),
    ("conv_channels", (), 4352),
    ("layer_state_bytes", (), 2_097_152),
    ("layer_tail_bytes", (2,), 26_112),
    # ISSUE 44: 36 x (64 x 64 x 128 x 4 + 3 x 4,352 x 2) bytes a sequence
    ("sequence_state_bytes", (2,), 76_437_504),
    # ... beside 4 layers x 2 x 8 heads x 64 x 2 bytes of pages a token
    ("page_token_bytes", (2,), 8_192),
])
def test_state_space_sizes_are_the_issues(what, args, want):
    from benchmarks.harness import costs_ssm
    hf = _granite()
    assert costs_ssm.has_state(hf)
    assert getattr(costs_ssm, what)(hf, *args) == want


def test_state_space_update_and_scan_by_hand():
    from benchmarks.harness import costs_ssm
    hf = _granite()
    # a decode tick of 64 rows reads and writes 64 x 2 x 75.5 MB = 9.66 GB
    # of state and 0.12 GB of convolution tails: 11.9 ms at 819 GB/s, beside
    # 7.8 ms for 6.38 GB of weights
    assert costs_ssm.update_bytes(64, hf, 2) \
        == 64 * 36 * 2 * (2_097_152 + 26_112) == 9_784_000_512
    assert costs_ssm.update_bytes(64, hf, 2) / 819e9 == pytest.approx(
        11.9e-3, rel=0.01)
    # one token: 5 operations a state element, 64 heads of [64, 128], 36
    # layers; one 2,048-token chunk: the rows' bytes bound it
    assert costs_ssm.scan_flops(1, hf) == 36 * 5 * 64 * 64 * 128
    assert costs_ssm.scan_bytes(1, 2048, hf, 2) == \
        36 * (2048 * 8512 * 2 + 2 * 2_097_152)
    least = costs_ssm.scan_least_seconds(1, 2048, hf, 2, 197e12, 819e9)
    assert least == costs_ssm.scan_bytes(1, 2048, hf, 2) / 819e9 \
        > costs_ssm.scan_flops(2048, hf) / 197e12
    assert not costs_ssm.has_state({"layer_types": ["full_attention"]})
    assert not costs_ssm.has_state({"num_hidden_layers": 2})
