"""The one metric ISSUE 51 adds to the benchmark, ``ssm_conv_share``: what a
decode tick spends under the program's ``ssm/conv`` scope, over device busy
time. Its reader on hand-built operations, in
``test_benchmark_granite_hybrid.py``'s style, and its entry by membership
(``benchmarks/README.md``): a later cell may join its list with no edit
here."""

import json
import pathlib

import pytest

from benchmarks.harness import cells
from benchmarks.harness import xplane_names as xn

REPO = pathlib.Path(__file__).resolve().parents[2]
NAME = "ssm_conv_share"
STATE_CELLS = ("granite4h-micro-serve-chat-many",
               "nemotron3-nano-serve-reasoning-many")
# whose programs hold no state kind: the metric is left out of their lines
OTHER_CELLS = ("mistral7b-train-8k", "mistral7b-serve-chat",
               "mixtral8x7b-serve-batch-rag", "joyai-flash-serve-docqa",
               "laguna-s-serve-code-mixed", "xing4-serve-reasoning",
               "mimo-v2.5-serve-agent-long")


class _Obs:
    device_kind = "TPU v5 lite"

    def __init__(self, config):
        self.cell = type("Cell", (), {"config": config})()
        self.trace = type("Trace", (), {"window": (0.0, 100.0)})()


def _config(bench, cell):
    name, = [w["config"] for w in bench["workloads"] if w["name"] == cell]
    file, = [c["file"] for c in bench["configs"] if c["name"] == name]
    return json.loads((REPO / file).read_text())


def _op(name, start, dur, scope, program="jit_decode_step_g", kernel=False):
    return xn.NamedOp(name, start, dur, 0, program,
                      'custom_call_target="tpu_custom_call"' if kernel else "",
                      "custom-call" if kernel else "fusion", False, scope)


def _entry(bench):
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    return entry


def test_entry_lists_the_state_cells_and_moves_tokens_per_second(bench):
    entry = _entry(bench)
    assert set(STATE_CELLS) <= set(entry["workloads"])
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "lower", "device_trace", "kernels",
                                "serve_tokens_per_s")
    tokens, = [m for m in bench["end_to_end"]
               if m["name"] == "serve_tokens_per_s"]
    assert set(entry["workloads"]) <= set(tokens["workloads"])


@pytest.mark.parametrize("cell", STATE_CELLS)
def test_reader_counts_the_decode_programs_conv_scope_alone(monkeypatch,
                                                            bench, cell):
    """The kernel and what XLA leaves around it under ``ssm/conv`` in a
    decode program count, 6 ms of 2 s busy; the update, the gated norm, the
    projections and a chunk program's convolution do not."""
    reader = cells.load_module(REPO, bench, "metrics", NAME)
    conv = "jit(decode_step_g)/jit(attend_decode)/ssm/conv/"
    ops = [_op("ssm_conv_step.4", 1.0, 0.004, conv + "jit(_conv_call)",
               kernel=True),
           _op("fusion.8", 1.1, 0.002, conv + "reshape"),
           _op("ssm_update.3", 1.2, 0.030,
               "jit(decode_step_g)/jit(attend_decode)/ssm/update/x",
               kernel=True),
           _op("fusion.6", 1.3, 0.040, "jit(decode_step_g)/ssm/norm/mul"),
           _op("fusion.5", 1.4, 0.500, "jit(decode_step_g)/ssm/in_proj/x"),
           _op("fusion.7", 1.9, 0.300, "jit(prefill_chunk_g)/ssm/conv/add",
               program="jit_prefill_chunk_g")]
    hf = _config(bench, cell)
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops, [0], 0.0))
    monkeypatch.setattr(reader.tr, "busy_seconds", lambda trace, window: 2.0)
    assert reader.read(_Obs(hf)) == pytest.approx(100 * 0.006 / 2.0)
    # two devices: the share is a device's
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops, [0, 1], 0.0))
    assert reader.read(_Obs(hf)) == pytest.approx(100 * 0.003 / 2.0)
    # a program without the scope in its decode step, no trace to read, and a
    # configuration that states no state layer: nothing, and no raise
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops[2:], [0], 0.0))
    assert reader.read(_Obs(hf)) is None
    monkeypatch.setattr(reader.named_readers, "named_ops", lambda obs: None)
    assert reader.read(_Obs(hf)) is None
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops, [0], 0.0))
    assert reader.read(_Obs({"layer_types": ["full_attention"]})) is None
    monkeypatch.setattr(reader.tr, "busy_seconds", lambda trace, window: 0.0)
    assert reader.read(_Obs(hf)) is None


def test_the_state_cells_configurations_state_a_state_layer_and_the_seven_others_none(
        bench):
    """Every state cell reads a configuration the reader takes and is IN the
    entry's list; the cells whose caches hold no state kind read one it
    leaves out."""
    from benchmarks.harness import costs_ssm
    for cell in STATE_CELLS:
        assert costs_ssm.has_state(_config(bench, cell)), cell
        assert cell in _entry(bench)["workloads"]
    for cell in OTHER_CELLS:
        assert not costs_ssm.has_state(_config(bench, cell)), cell
