"""The files ISSUE 52 adds to the benchmark, rehearsed on the CPU: the
``lfm2_moe`` family and reference, a toy configuration (four tail slots) and
traffic mix, the two new metric readers and the cost functions beside them,
through the same runner and result line as ``benchmarks/run.py`` and with no
edit to a file that was there (``benchmark_rehearsal.py``). What a test says
of ``BENCHMARK.json`` it says by membership (``benchmarks/README.md``)."""

import json
import shutil

import pytest

import benchmark_rehearsal as rehearsal
import test_benchmark_contract as contract
from benchmarks.harness import cells
from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn

REPO = rehearsal.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL, CONFIG, MIX = "tiny-toolcalls-many", "tiny-lfm2-moe", \
    "tiny-toolcalls-many"
LIKE = "lfm2-24b-serve-toolcalls-many"
REAL_NAME = "lfm2-24b-a2b-serve-d10"
REAL = REPO / "benchmarks" / "configs" / f"{REAL_NAME}.json"
NEW = ("short_conv_share", "conv_shift_roofline")
JOINED = ("moe_grouped_share", "moe_grouped_roofline",
          "moe_rows_per_touched_expert", "full_attn_share",
          "kv_bytes_per_live_token", "state_slots_fill",
          "prefill_tokens_per_tick", "prefill_bucket_fill",
          "prefill_idle_attributed_share", "idle_with_work_share",
          "step_starved_share", "step_ahead_share", "host_gc_pause_max_ms",
          "decode_rows_fused_share")
HF = json.loads(REAL.read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal's checkout plus the toy cell, as files and entries."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench_lfm2"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    data = json.loads((rehearsal.DATA / f"{CONFIG}.json").read_text())
    shutil.copy(rehearsal.DATA / f"{CONFIG}.json",
                root / "benchmarks" / "configs" / f"{CONFIG}.json")
    shutil.copy(rehearsal.DATA / f"{MIX}.json",
                root / "benchmarks" / "traffic" / f"{MIX}.json")
    bench["configs"].append({
        "name": CONFIG, "source": data["source"], "reduced": data["reduced"],
        "why": "toy", "file": f"benchmarks/configs/{CONFIG}.json"})
    bench["workloads"].append({"name": CELL, "config": CONFIG, "traffic": MIX,
                               "why": "toy", "chips": 1})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"] = metric["workloads"] + [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.fixture(scope="module")
def runs(root):
    from deepspeed_tpu.telemetry.tracer import get_tracer
    out = {}
    for traced in (False, True):
        lines = []
        obs, line = rehearsal.run_cell(root, CELL, 2.0, traced, lines=lines)
        out[traced] = (obs, line, lines)
    yield out
    get_tracer().configure(enabled=False)
    get_tracer().clear()


def test_nothing_that_was_there_is_edited(root):
    assert rehearsal.files_that_differ(root) == []


@pytest.mark.parametrize("config", [
    f"benchmarks/configs/{REAL_NAME}.json",
    f"tests/benchmarks/data/{CONFIG}.json"])
def test_configuration_keeps_the_rule(config):
    data = json.loads((REPO / config).read_text())
    entry = {"name": "x", "file": config, "why": "x",
             "source": data["source"], "reduced": data["reduced"]}
    assert contract.configuration_faults(entry, data) == []
    assert set(data["published"]) == set(data["reduced"])


def test_configuration_is_the_catalog_row_cut_in_depth_alone(bench):
    """Every key of the published config at its published value but the one
    that ``reduced`` names (the row is copied here: the catalog lies outside
    the repository), every width among them."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k for k, v in published.items()
            if HF.get(k, "absent") != v} == set()
    whole = ["conv", "conv", "full_attention", "conv"] * 10
    assert HF["layer_types"] == whole[:10] and HF["num_hidden_layers"] == 10
    assert (HF["layer_types"].count("conv"),
            HF["layer_types"].count("full_attention")) == (8, 2)
    assert HF["reduced"] == ["num_hidden_layers"]
    assert HF["published"] == {"num_hidden_layers": 40}
    assert "head_dim" not in HF and "tie_word_embeddings" not in HF
    assert len(HF["assumed"]) >= 10
    for said in ("tie_word_embeddings", "head_dim", "11,776", "1e-6",
                 "steers the choice ONLY", "_DealtBias", "_DrawnHeadNorms",
                 "BEFORE rope",
                 "NO activation", "no recurrent state", "embedding_norm",
                 "multi-token", "model.layers.N.conv"):
        assert any(said.lower() in text.lower() for text in HF["assumed"]), \
            said
    entry, = [c for c in bench["configs"] if c["name"] == REAL_NAME]
    assert entry["source"] == HF["source"] \
        and entry["reduced"] == HF["reduced"]
    assert entry["file"] == f"benchmarks/configs/{REAL_NAME}.json"
    cell, = [w for w in bench["workloads"] if w["name"] == LIKE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (REAL_NAME, "toolcalls-many", 1)
    for number in ("5,267,090,176", "16,783,360", "10,485,888", "72,351,744",
                   "604,110,912", "134,219,776"):
        assert number in HF["deployment"], number
    for said in ("kv_sizing", "logit_margin_why", "check_why", "engine_why"):
        assert HF["serve"][said]


def test_traffic_is_the_issues_letter_for_letter():
    from benchmarks.harness import traffic
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "toolcalls-many.json").read_text())
    assert {k: mix[k] for k in ("kind", "loop", "clients", "max_concurrency",
                                "block_requests", "ramp_s", "drain_s")} == {
        "kind": "serve", "loop": "closed", "clients": 256,
        "max_concurrency": 256, "block_requests": 256, "ramp_s": 20.0,
        "drain_s": 20.0}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 0.6, "min": 128, "max": 2048}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.6, "min": 32, "max": 512}
    assert "order_seed" not in mix and "rate_rps" not in mix
    prompts = traffic.quantile_lengths(mix["prompt_tokens"], 256)
    outputs = traffic.quantile_lengths(mix["output_tokens"], 256)
    # most credited tokens are prompts, and a request is short
    share = prompts.sum() / (prompts.sum() + outputs.sum())
    assert 0.8 < share < 0.9 and 130 < outputs.mean() < 170
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest == 40 * 64 == HF["serve"]["max_context"]
    assert mix["blocks"] >= 24
    # a window holds thousands of requests: the longest is under 0.2% of
    # six blocks' tokens and the mean one under 0.1%
    whole = (prompts + outputs).sum() * 6
    assert (prompts + outputs).max() / whole < 2e-3
    assert (prompts + outputs).mean() / whole < 1e-3


def test_metrics_list_the_cell_and_move_tokens_per_second(bench):
    """Membership only: a later cell may join any of these lists, and this
    one any list it reports, with no edit here."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    share, roofline = (by_name[name] for name in NEW)
    assert (share["moves"], share["layer"], share["source"], share["unit"],
            share["better"]) == ("serve_tokens_per_s", "model step",
                                 "device_trace", "%", "lower")
    assert (roofline["moves"], roofline["layer"], roofline["source"],
            roofline["unit"], roofline["better"]) == (
        "serve_tokens_per_s", "kernels", "device_trace", "%", "higher")
    for name in NEW + JOINED:
        assert LIKE in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    tokens, = [m for m in bench["end_to_end"]
               if m["name"] == "serve_tokens_per_s"]
    assert LIKE in tokens["workloads"]


def test_family_builds_the_published_widths_and_a_tail_only_slot():
    family = cells.load_module(REPO, BENCH, "families", "lfm2_moe")
    cfg, model = family.build(HF, "serve", HF["serve"])
    assert (cfg.num_layers, cfg.vocab_size, cfg.hidden_size) == \
        (10, 65536, 2048)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.kv_pack) == \
        (32, 8, 64, 2)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.held, cfg.num_experts_per_tok, cfg.num_dense_layers,
            cfg.conv_width, cfg.routed_scaling_factor, cfg.rope_theta) == \
        (11776, 1536, 64, 64, 4, 2, 3, 1.0, 1e6)
    assert cfg.max_seq_len == HF["serve"]["max_context"] == 2560
    engine = HF["serve"]["engine"]
    assert engine["max_tracked_sequences"] == 256 \
        == engine["scheduler"]["max_decode_batch"] \
        == max(engine["decode_batch_buckets"])
    assert max(engine["ctx_block_buckets"]) == 40
    assert engine["kv_num_blocks"] <= 256 * 40 + 1
    # the check crosses a chunk boundary with a tail in hand, and holds
    # prompts shorter than the tail
    check = HF["serve"]["check"]
    assert check["prompt"] > max(engine["scheduler"]["prefill_buckets"])
    assert {1, 2} <= set(check["others"])
    # ... and its wave holds every slot, so it decodes in the program the
    # window's 256 callers sit in (one more row than the bucket below holds
    # would do for the program; all of them make every slot live)
    buckets = sorted(engine["decode_batch_buckets"])
    assert 1 + len(check["others"]) == engine["max_tracked_sequences"] \
        > buckets[-2]
    assert check["prompt"] + check["new_tokens"] \
        <= 64 * max(engine["ctx_block_buckets"]) <= HF["serve"]["max_context"]
    # what the cache holds: the issue's bytes
    from deepspeed_tpu.inference.v2.modules import policy_for
    spec = policy_for(cfg).cache_spec(cfg)
    assert (spec.layer_kinds.count("state"),
            spec.layer_kinds.count("full")) == (8, 2)
    assert spec.state_slot.layer_bytes(2) == 8192
    assert not spec.state_slot.recurrent
    assert (spec.num_kv_heads, spec.head_dim) == (4, 128)
    costs = cells.load_module(REPO, BENCH, "harness", "costs_short_conv")
    assert costs.sequence_tail_bytes(HF, 2) == 65536
    with pytest.raises(ValueError):
        family.build(HF, "train", {})


def test_family_deals_every_router_one_ladder_of_biases():
    """What decides how many experts a tick touches is the same for every
    seed and every layer: one ladder of N(0, 0.1) quantiles, in an order of
    the seed's, and none of them zero."""
    import statistics

    import jax
    import numpy as np
    family = cells.load_module(REPO, BENCH, "families", "lfm2_moe")
    toy = json.loads((rehearsal.DATA / f"{CONFIG}.json").read_text())
    _, model = family.build(toy, "serve", toy["serve"])
    example = {"input_ids": np.zeros((1, 8), np.int32)}
    ladder = np.array([statistics.NormalDist(0, 0.1).inv_cdf((j + 0.5) / 8)
                       for j in range(8)], np.float32)
    dealt = []
    for seed in (3, 4):
        got = jax.jit(model.init)(jax.random.PRNGKey(seed), example)["params"]
        assert "moe" not in got["layer_0"] and "moe" not in got["layer_1"]
        for name in ("layer_2", "layer_3", "layer_5"):
            bias = np.asarray(got[name]["moe"]["gate"]["bias"])
            assert bias.dtype == np.float32 and bias.shape == (8,)
            np.testing.assert_allclose(np.sort(bias), ladder, rtol=1e-6)
            assert float(np.abs(bias).min()) > 0
            dealt.append(bias)
    assert len({tuple(bias) for bias in dealt}) == 6
    # and a head norm's scales lie about 1 and not on it, so that a program
    # that skipped the norm or its scale computes other scores
    for which in ("q_norm", "k_norm"):
        scale = np.asarray(got["layer_2"]["attn"][which])
        assert scale.shape == (64,) and 0.5 <= scale.min() < 0.9 \
            and 1.1 < scale.max() <= 2.0


def test_controls_break_the_program_where_they_say_at_toy_widths():
    """``benchmarks/controls/lfm2_moe.py`` patches the program's own
    functions: the sound program agrees; Mamba's silu left in, the
    convolution over ``v`` alone and one row kept of two differ, through the
    harness's own comparison, and nothing stays patched. (The toy's limit is
    0.4: with 8 experts 1/8 of a draw apart and 2 chosen, sound runs read
    0.06-0.16 over seeds 7-9 on the CPU, the float8 reference 0.96-1.65, the
    three structural faults 4.9-5.9.)"""
    from deepspeed_tpu.inference.v2 import kv_cache, modules
    from deepspeed_tpu.models import lfm2_moe
    from deepspeed_tpu.ops.pallas import ssm_update
    controls = cells.load_module(REPO, BENCH, "controls", "lfm2_moe")
    toy = json.loads((rehearsal.DATA / f"{CONFIG}.json").read_text())
    toy["serve"] = {**toy["serve"],
                    "check": {**toy["serve"]["check"], "new_tokens": 24}}

    def patched():
        return (kv_cache._TailSlots._tail_written,
                kv_cache._TailSlots._decode_conv,
                kv_cache._TailSlots.activation, ssm_update.unpack_tail,
                modules._short_conv_mixer, lfm2_moe.head_norm, lfm2_moe.route)
    before = patched()
    lines = []
    names = ("sound", "fp8_matrices", "silu_conv", "no_b_gate", "tail_short")
    held = controls.run(toy, BENCH, 7, names, lines.append)
    assert held["sound"] and not held["silu_conv"] \
        and not held["no_b_gate"] and not held["tail_short"]
    said = [json.loads(line) for line in lines]
    assert tuple(one["control"] for one in said) == names
    margin = {one["control"]: float(
        one["line"].split("token) ")[1].split(",")[0]) for one in said}
    assert margin["fp8_matrices"] > 3 * margin["sound"]
    assert before == patched()
    assert set(controls.CONTROLS) >= {
        "fp8_tail", "no_c_gate", "no_qk_norm", "bias_ignored",
        "softmax_router"}


@pytest.mark.parametrize("traced", [False, True])
def test_toy_cell_is_correct_and_compiles_nothing_in_the_window(runs, traced):
    obs, line, lines = runs[traced]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert obs.counters["compiles_in_window"] == 0
    assert any("reference check" in text and "agree" in text for text in lines)
    assert "serve_tokens_per_s" in line["metrics"] or traced


def test_traced_run_reports_the_program_counter_metrics(runs):
    """Device-trace metrics need a TPU plane: their readers find nothing on
    the CPU, return None and are left out, as on a program without them."""
    _, line, _ = runs[True]
    assert 0 < line["metrics"]["state_slots_fill"]["value"] <= 100
    assert 0 < line["metrics"]["moe_rows_per_touched_expert"]["value"]
    assert 0 < line["metrics"]["kv_bytes_per_live_token"]["value"]
    assert not any(k.endswith("_roofline") or k == "short_conv_share"
                   for k in line["metrics"])


def test_every_joined_reader_reads_the_toy_run_without_raising(runs):
    obs, _, _ = runs[True]
    for name in NEW + JOINED:
        reader = cells.load_module(REPO, BENCH, "metrics", name)
        value = reader.read(obs)
        assert value is None or value >= 0, name


def test_spans_count_rows_and_touched_experts_behind_every_mixer(runs):
    evs = [e for e in pe.events() if e.name in (pe.PREFILL_CHUNK,
                                                pe.STEP_DECODE)
           and e.arg("expert_rows") is not None]
    assert evs
    # every expert is held: no assignment falls on an absent one
    assert not any(e.arg("expert_rows_absent") for e in evs)
    # at most the 8 experts of each of the 4 expert layers, in each of the
    # programs a span's counts are of
    assert min(e.arg("experts_touched") for e in evs) <= 4 * 8


# --- the costs, on hand-counted shapes -------------------------------------------

def test_short_conv_costs_on_hand_counted_shapes():
    costs = cells.load_module(REPO, BENCH, "harness", "costs_short_conv")
    assert costs.has_short_conv(HF) and costs.conv_layers(HF) == 8
    # 2 rows x 2,048 channels x 2 bytes a layer a sequence
    assert costs.layer_tail_bytes(HF, 2) == 8192
    assert costs.sequence_tail_bytes(HF, 2) == 8 * 8192
    # a decode tick of 256 rows: 8 layers x (tail read and written 2 x 8,192
    # + the row in and out 2 x 4,096) = 24,576 bytes a row a layer
    assert costs.shift_bytes(1, HF, 2) == 8 * 24576
    assert costs.shift_bytes(256, HF, 2) == 256 * 8 * 24576 == 50_331_648
    toy = {"layer_types": ["conv", "full_attention", "conv"],
           "conv_L_cache": 4, "hidden_size": 10}
    assert costs.shift_bytes(3, toy, 4) == 3 * 2 * 2 * (3 * 10 * 4 + 10 * 4)
    granite = json.loads((REPO / "benchmarks" / "configs" /
                          "granite-4.0-h-micro-serve.json").read_text())
    assert not costs.has_short_conv(granite)
    assert not costs.has_short_conv({"layer_types": ["conv"]})


# --- the readers, on hand-built ticks --------------------------------------------

class _Obs:
    device_kind = "TPU v5 lite"
    window = (0.0, 100.0)

    def __init__(self, config):
        self.cell = type("Cell", (), {"config": config})()
        self.trace = type("Trace", (), {"window": (0.0, 100.0)})()


def _op(name, start, dur, scope, program="jit_decode_step_g"):
    return xn.NamedOp(name, start, dur, 0, program, "", "fusion", False,
                      scope)


_SHIFT = "jit(decode_step_g)/conv/shift/jit(attend_decode)/jit(_conv_call)/x"


def test_conv_shift_roofline_reader_on_hand_built_ticks(monkeypatch):
    reader = cells.load_module(REPO, BENCH, "metrics", "conv_shift_roofline")

    def ev(name, t0, dur, tick, **args):
        return pe.Event(name, t0, dur, args={"tick": tick, **args})
    evs = [ev(pe.STEP_DECODE, 1.0, 0.2, 1, batch=256),
           ev(pe.DECODE_WAIT, 1.2, 0.3, 1),
           ev(pe.PREFILL_CHUNK, 2.0, 0.1, 2),
           ev(pe.STEP_DECODE, 2.1, 0.1, 2, batch=200),
           ev(pe.DECODE_WAIT, 2.3, 0.2, 2)]
    chunk = _SHIFT.replace("decode_step_g", "prefill_chunk_g")
    ops = [_op("ssm_conv_step.1", 1.05, 0.0004, _SHIFT),
           _op("ssm_conv_step.1", 2.15, 0.0004, _SHIFT),
           # a Mamba layer's shift, a chunk's, a projection: not counted
           _op("ssm_conv_step.2", 1.1, 0.5,
               "jit(decode_step_g)/ssm/conv/jit(_conv_call)/x"),
           _op("fusion.1", 2.05, 0.5, chunk, program="jit_prefill_chunk_g"),
           _op("fusion.2", 1.3, 0.5, "jit(decode_step_g)/conv/in_proj/dot"),
           _op("ssm_conv_step.1", 0.2, 0.5, _SHIFT)]  # outside every tick
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops, [0], 0.0))
    monkeypatch.setattr(reader.pe, "events", lambda: evs)
    need = (256 + 200) * 8 * 24576
    assert reader.read(_Obs(HF)) == pytest.approx(
        100 * need / 819e9 / 0.0008)
    # another family reads nothing here, nor a program without the scope, a
    # run without decode ticks, or one without a device trace
    granite = json.loads((REPO / "benchmarks" / "configs" /
                          "granite-4.0-h-micro-serve.json").read_text())
    assert reader.read(_Obs(granite)) is None
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops[2:5], [0], 0.0))
    assert reader.read(_Obs(HF)) is None
    monkeypatch.setattr(reader.pe, "events", lambda: [
        ev(pe.PREFILL_CHUNK, 1.0, 0.2, 1), ev(pe.DECODE_WAIT, 1.2, 0.3, 1)])
    assert reader.read(_Obs(HF)) is None
    monkeypatch.setattr(reader.named_readers, "named_ops", lambda obs: None)
    assert reader.read(_Obs(HF)) is None


def test_short_conv_share_reader_on_hand_built_operations(monkeypatch):
    reader = cells.load_module(REPO, BENCH, "metrics", "short_conv_share")
    ops = [_op("fusion.1", 1.0, 0.02, "jit(decode_step_g)/conv/in_proj/dot"),
           _op("ssm_conv_step.1", 1.1, 0.01, _SHIFT),
           _op("fusion.2", 1.2, 0.03, "jit(prefill_chunk_g)/conv/out_proj/dot",
               program="jit_prefill_chunk_g"),
           # a Mamba layer's convolution and the experts are not the mixer's
           _op("ssm_conv_step.2", 1.3, 0.5,
               "jit(decode_step_g)/ssm/conv/jit(_conv_call)/x"),
           _op("fusion.3", 1.9, 0.5, "jit(decode_step_g)/moe/experts/x")]
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops, [0], 0.0))
    monkeypatch.setattr(reader.tr, "busy_seconds", lambda trace, window: 1.2)
    assert reader.read(_Obs(HF)) == pytest.approx(100 * 0.06 / 1.2)
    granite = json.loads((REPO / "benchmarks" / "configs" /
                          "granite-4.0-h-micro-serve.json").read_text())
    assert reader.read(_Obs(granite)) is None
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops[3:], [0], 0.0))
    assert reader.read(_Obs(HF)) is None
    monkeypatch.setattr(reader.named_readers, "named_ops", lambda obs: None)
    assert reader.read(_Obs(HF)) is None
