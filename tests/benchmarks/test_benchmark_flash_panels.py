"""``flash_masked_panel_share``: the panels of the flash forward kernel that
the diagonal or the window's edge crosses over its panels that compute, from
the program's own ``flash_attention.panels`` at the cell's shapes; and where
``BENCHMARK.json`` lists it."""

import pathlib
import sys
import types

import pytest

from benchmarks.harness import trace as tr
from benchmarks.harness.cells import load_module
from benchmarks.harness.observations import Observations

REPO = pathlib.Path(__file__).resolve().parents[2]
NAME = "flash_masked_panel_share"
MODULE = "deepspeed_tpu.ops.pallas.flash_attention"
HF = {"hidden_size": 4096, "num_attention_heads": 32, "sliding_window": 4096,
      "train": {"attention_backend": "flash"}}


@pytest.fixture
def read(bench):
    reader = load_module(REPO, bench, "metrics", NAME)

    def run(hf=HF, seq_len=8192, on_a_device=True, train=True):
        obs = Observations(kind="train", cell=types.SimpleNamespace(config=hf),
                           devices=[])
        obs.train = {"seq_len": seq_len, "steps": 3} if train else {}
        obs.trace = tr.Trace([], [], [0] if on_a_device else [],
                             window=(50.5, 51.5))
        return reader.read(obs)
    return run


def test_the_share_is_the_programs_own_count(read):
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    counts = fa.panels(8192, 8192, True, 4096, head_dim=128)
    assert read() == pytest.approx(100.0 * counts["flash_panels_masked"]
                                   / counts["flash_panels"])
    assert 0.0 < read() < 100.0
    # without a window the band is the triangle: fewer whole panels a row
    plain = dict(HF, sliding_window=None)
    counts = fa.panels(8192, 8192, True, None, head_dim=128)
    assert read(plain) == pytest.approx(100.0 * counts["flash_panels_masked"]
                                        / counts["flash_panels"])


def test_twelve_of_thirty_at_1024_and_twenty_four_of_108_at_512(
        read, monkeypatch):
    """The parent's panel of 1,024 x 1,024 (ISSUE 50's figure), and the
    forward's own of 512 x 512 since PR 50's sweep."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    assert fa._auto_panel(8192, 8192, 128) == (512, 512)
    assert read() == pytest.approx(100.0 * 24 / 108)
    monkeypatch.setattr(fa, "_auto_panel", lambda sq, sk, d: (1024, 1024))
    assert read() == pytest.approx(40.0)


def test_nothing_to_read_is_none_and_never_raises(read, monkeypatch):
    assert read(on_a_device=False) is None
    assert read(train=False) is None
    assert read(dict(HF, train={"attention_backend": "xla"})) is None
    assert read({k: v for k, v in HF.items() if k != "train"}) is None
    # a program older than PR 50: the module has no ``panels``
    monkeypatch.setitem(sys.modules, MODULE, types.ModuleType(MODULE))
    assert read() is None


def test_the_entry_and_the_cell_that_reports_it(bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "kernels",
                                 "train_tokens_per_s_per_chip", "%", "lower")
    (moved,) = [m for m in bench["end_to_end"]
                if m["name"] == "train_tokens_per_s_per_chip"]
    assert "mistral7b-train-8k" in entry["workloads"]
    assert "mistral7b-train-8k" in moved["workloads"]
    # beside the kernels' rooflines, which the same cell reports
    for roofline in ("flash_fwd_roofline", "flash_bwd_roofline"):
        (other,) = [m for m in bench["per_layer"] if m["name"] == roofline]
        assert set(entry["workloads"]) <= set(other["workloads"])
