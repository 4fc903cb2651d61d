"""The files ISSUE 49 adds to the benchmark, rehearsed on the CPU: the
``nemotron_h`` family and reference, a toy configuration (one device's half
of 16 experts, four state slots) and traffic mix, the new metric reader and
the cost functions beside it, through the same runner and result line as
``benchmarks/run.py`` and with no edit to a file that was there
(``benchmark_rehearsal.py``). What a test says of ``BENCHMARK.json`` it says
by membership (``benchmarks/README.md``)."""

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
CELL, CONFIG, MIX = "tiny-reasoning-many", "tiny-nemotron-h", \
    "tiny-reasoning-many"
LIKE = "nemotron3-nano-serve-reasoning-many"
REAL_NAME = "nemotron-3-nano-serve-d13-e64"
REAL = REPO / "benchmarks" / "configs" / f"{REAL_NAME}.json"
JOINED = ("moe_grouped_share", "moe_rows_per_touched_expert",
          "ssm_update_roofline", "ssm_scan_roofline", "ssm_share",
          "state_slots_fill", "full_attn_share", "kv_bytes_per_live_token",
          "prefill_tokens_per_tick", "prefill_idle_attributed_share",
          "prefill_bucket_fill", "idle_with_work_share", "step_starved_share",
          "step_ahead_share", "host_gc_pause_max_ms",
          "decode_rows_fused_share")
HF = json.loads(REAL.read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal's checkout plus the toy cell, as files and entries."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench_nemotron"))
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
    assert data["deployment_chips"] == 2


def test_configuration_is_the_catalog_row_cut_in_depth_and_experts(bench):
    """Every key of the published config at its published value but the two
    that ``reduced`` names (the row is copied here: the catalog lies outside
    the repository), every width among them."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    assert {k for k, v in published.items()
            if HF.get(k, "absent") != v} == set()
    whole = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert HF["hybrid_override_pattern"] == whole[:13] == "MEMEM*EMEMEM*"
    assert (HF["num_hidden_layers"], HF["n_routed_experts"]) == (13, 64)
    assert HF["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert HF["published"] == {"num_hidden_layers": 52,
                               "n_routed_experts": 128}
    assert HF["first_expert_held"] == 0
    # granite's key names, which harness/costs_ssm.py reads: derived twins
    assert (HF["mamba_n_heads"], HF["mamba_d_head"], HF["mamba_d_state"],
            HF["mamba_d_conv"], HF["mamba_n_groups"]) == (
        HF["mamba_num_heads"], HF["mamba_head_dim"], HF["ssm_state_size"],
        HF["conv_kernel"], HF["n_groups"])
    names = {"M": "mamba", "E": "moe", "*": "attention"}
    assert HF["layer_types"] == [names[c]
                                 for c in HF["hybrid_override_pattern"]]
    assert len(HF["assumed"]) >= 10
    for said in ("positional", "expand", "silu(z) BEFORE", "softplus",
                 "float32", "_LongMemory", "_QuietRouted", "e_score_correction_bias",
                 "derived", "multi-token", "backbone.layers"):
        assert any(said.lower() in text.lower() for text in HF["assumed"]), \
            said
    entry, = [c for c in bench["configs"] if c["name"] == REAL_NAME]
    assert entry["source"] == HF["source"] \
        and entry["reduced"] == HF["reduced"]
    assert entry["file"] == f"benchmarks/configs/{REAL_NAME}.json"
    cell, = [w for w in bench["workloads"] if w["name"] == LIKE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (REAL_NAME, "reasoning-many", 1)
    for number in ("4,278,340,096", "31,577,940,288", "38,744,896",
                   "658,885,376", "23,399,040", "704,645,760"):
        assert number in HF["deployment"], number


def test_traffic_is_the_issues_letter_for_letter():
    from benchmarks.harness import traffic
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "reasoning-many.json").read_text())
    assert {k: mix[k] for k in ("kind", "loop", "clients", "max_concurrency",
                                "block_requests", "drain_s")} == {
        "kind": "serve", "loop": "closed", "clients": 128,
        "max_concurrency": 128, "block_requests": 128, "drain_s": 20.0}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.7, "min": 64, "max": 1024}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 512,
                                    "max": 2048}
    assert "order_seed" not in mix and "rate_rps" not in mix
    prompts = traffic.quantile_lengths(mix["prompt_tokens"], 128)
    outputs = traffic.quantile_lengths(mix["output_tokens"], 128)
    # most tokens are decoded: about a quarter of the credited are prompts
    share = prompts.sum() / (prompts.sum() + outputs.sum())
    assert 0.2 < share < 0.3 and 1250 < outputs.mean() < 1310
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest == 48 * 64 == HF["serve"]["max_context"]
    # no caller runs out: the blocks hold twice what ramp and window serve
    served = mix["blocks"] * mix["block_requests"] \
        * (prompts.mean() + outputs.mean())
    assert served > 2 * 9000 * (mix["ramp_s"] + 51)


def test_metrics_list_the_cell_and_move_tokens_per_second(bench):
    """Membership only: a later cell may join any of these lists, and this
    one any list it reports, with no edit here."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    new = by_name["moe_ungated_roofline"]
    assert LIKE in new["workloads"]
    assert (new["moves"], new["layer"], new["source"], new["unit"],
            new["better"]) == ("serve_tokens_per_s", "model step",
                               "device_trace", "%", "higher")
    for name in JOINED:
        assert LIKE in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    tokens, = [m for m in bench["end_to_end"]
               if m["name"] == "serve_tokens_per_s"]
    assert LIKE in tokens["workloads"]


def test_family_builds_the_published_widths_and_the_held_share():
    family = cells.load_module(REPO, BENCH, "families", "nemotron_h")
    cfg, model = family.build(HF, "serve", HF["serve"])
    assert (cfg.num_layers, cfg.vocab_size, cfg.hidden_size) == \
        (13, 131072, 2688)
    assert (cfg.pattern.count("M"), cfg.pattern.count("E"),
            cfg.pattern.count("*")) == (6, 5, 2)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state,
            cfg.mamba_groups, cfg.mamba_d_conv, cfg.mamba_chunk_size,
            cfg.d_inner, cfg.conv_channels, cfg.in_proj_width) == \
        (64, 64, 128, 8, 4, 128, 4096, 6144, 10304)
    assert (cfg.moe_intermediate_size, cfg.shared_intermediate_size,
            cfg.n_routed_experts, cfg.held, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor) == \
        (1856, 3712, 128, 64, 0, 6, 2.5)
    assert cfg.max_seq_len == HF["serve"]["max_context"] == 3072
    engine = HF["serve"]["engine"]
    assert engine["max_tracked_sequences"] == 128 \
        == engine["scheduler"]["max_decode_batch"] \
        == max(engine["decode_batch_buckets"])
    assert engine["kv_num_blocks"] == 128 * 48 + 1
    # the check crosses a chunk boundary with a state in hand, off a block
    check = HF["serve"]["check"]
    assert check["prompt"] > max(engine["scheduler"]["prefill_buckets"])
    assert check["prompt"] % 128 and check["new_tokens"] >= 1000
    assert min(check["others"]) <= 32
    assert check["prompt"] + check["new_tokens"] \
        <= 64 * max(engine["ctx_block_buckets"]) <= HF["serve"]["max_context"]
    # what the cache holds: the issue's bytes
    from deepspeed_tpu.inference.v2.modules import policy_for
    spec = policy_for(cfg).cache_spec(cfg)
    assert (spec.layer_kinds.count("state"), spec.layer_kinds.count("full"),
            spec.layer_kinds.count("none")) == (6, 2, 5)
    assert spec.state_slot.layer_bytes(2) == 2_134_016
    assert spec.state_slot.stored == (32, 128, 128)
    assert (spec.num_kv_heads, spec.head_dim) == (2, 128)
    with pytest.raises(ValueError):
        family.build(HF, "train", {})


def test_family_draws_heads_that_remember_and_a_bias_that_is_not_zero():
    import jax
    import numpy as np
    from deepspeed_tpu.models import granite_hybrid as gh
    family = cells.load_module(REPO, BENCH, "families", "nemotron_h")
    toy = json.loads((rehearsal.DATA / f"{CONFIG}.json").read_text())
    cfg, model = family.build(toy, "serve", toy["serve"])
    assert (cfg.n_routed_experts, cfg.held, cfg.first_expert) == (16, 8, 8)
    got = model.init(jax.random.PRNGKey(3),
                     {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    for name in ("layer_0", "layer_2", "layer_5"):
        mp = got[name]["mamba"]
        assert np.all(np.exp(mp["a_log"]) == gh.A_RANGE[0])
        np.testing.assert_allclose(jax.nn.softplus(mp["dt_bias"]),
                                   gh.DT_MIN, rtol=1e-4)
    moe = got["layer_1"]["moe"]
    assert moe["gate"]["kernel"].shape == (64, 16)
    assert float(np.abs(moe["gate"]["bias"]).min()) > 0
    assert moe["experts"]["w_in"].shape == moe["experts"]["w_down"].shape \
        == (8, 24, 64)


def test_family_deals_every_share_of_a_router_one_ladder_of_biases():
    """What decides how many of a chip's experts a tick touches is the same
    for every seed, every layer and both chips of the pair: one ladder of
    N(0, 0.1) quantiles, in an order of the seed's."""
    import statistics
    import jax
    import numpy as np
    family = cells.load_module(REPO, BENCH, "families", "nemotron_h")
    toy = json.loads((rehearsal.DATA / f"{CONFIG}.json").read_text())
    _, model = family.build(toy, "serve", toy["serve"])
    example = {"input_ids": np.zeros((1, 8), np.int32)}
    ladder = np.array([statistics.NormalDist(0, 0.1).inv_cdf((j + 0.5) / 8)
                       for j in range(8)], np.float32)
    dealt = []
    for seed in (3, 4):
        got = jax.jit(model.init)(jax.random.PRNGKey(seed), example)["params"]
        for name in ("layer_1", "layer_4"):
            bias = np.asarray(got[name]["moe"]["gate"]["bias"])
            assert bias.dtype == np.float32 and bias.shape == (16,)
            for share in (bias[:8], bias[8:]):
                np.testing.assert_allclose(np.sort(share), ladder, rtol=1e-6)
            dealt.append(bias)
    assert len({tuple(bias) for bias in dealt}) == 4


def test_controls_break_the_program_where_they_say_at_toy_widths():
    """``benchmarks/controls/nemotron_h.py`` patches the program's own
    functions: the sound program agrees, ``relu`` for ``relu^2``, group 0's B
    and C for every head and the shared expert left out differ, through the
    harness's own comparison, and nothing stays patched."""
    from deepspeed_tpu.inference.v2 import kv_cache, modules
    from deepspeed_tpu.ops.pallas import grouped_matmul as gmm
    controls = cells.load_module(REPO, BENCH, "controls", "nemotron_h")
    toy = json.loads((rehearsal.DATA / f"{CONFIG}.json").read_text())
    # the toy's own limit: a sound run in bfloat16 over a held share reads
    # 0.017 (one near-tie of the toy's 512 logits), the controls 0.35 and up
    toy["serve"] = {**toy["serve"],
                    "check": {**toy["serve"]["check"], "new_tokens": 24}}
    before = (kv_cache.ssm.split_conv, modules._granite.gated_norm,
              modules.route, modules.relu2, gmm.grouped_relu2_in,
              kv_cache._StateSlots.empty)
    lines = []
    names = ("sound", "fp8_matrices", "relu", "group0_bc", "no_shared")
    held = controls.run(toy, BENCH, 7, names, lines.append)
    assert held["sound"] and not held["relu"] and not held["group0_bc"] \
        and not held["no_shared"]
    said = [json.loads(line) for line in lines]
    assert tuple(one["control"] for one in said) == names
    margin = {one["control"]: float(
        one["line"].split("token) ")[1].split(",")[0]) for one in said}
    assert margin["fp8_matrices"] > 3 * margin["sound"]
    assert before == (kv_cache.ssm.split_conv, modules._granite.gated_norm,
                      modules.route, modules.relu2, gmm.grouped_relu2_in,
                      kv_cache._StateSlots.empty)
    assert set(controls.CONTROLS) >= {
        "bf16_state", "whole_norm", "bias_ignored", "no_scaling"}


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
    # half the router's choices fall on the other device's experts
    assert 0 < line["metrics"]["moe_rows_per_touched_expert"]["value"]
    assert not any(k.endswith("_roofline") or k == "ssm_share"
                   for k in line["metrics"])


def test_every_joined_reader_reads_the_toy_run_without_raising(runs):
    obs, _, _ = runs[True]
    for name in ("moe_ungated_roofline",) + JOINED:
        reader = cells.load_module(REPO, BENCH, "metrics", name)
        value = reader.read(obs)
        assert value is None or value >= 0, name


def test_spans_count_the_rows_absent_experts_would_have_taken(runs):
    evs = [e for e in pe.events() if e.name in (pe.PREFILL_CHUNK,
                                                pe.STEP_DECODE)
           and e.arg("expert_rows") is not None]
    assert evs
    assert any(e.arg("expert_rows_absent") for e in evs)
    # at most the 8 held experts of each of the 2 expert layers, in each of
    # a tick's programs
    assert min(e.arg("experts_touched") for e in evs) <= 2 * 8


# --- the reader, on hand-built ticks -------------------------------------------

class _Obs:
    device_kind = "TPU v5 lite"
    window = (0.0, 100.0)

    def __init__(self, config):
        self.cell = type("Cell", (), {"config": config})()
        self.trace = type("Trace", (), {"window": (0.0, 100.0)})()


def _op(name, start, dur, scope, program="jit_decode_step_g"):
    return xn.NamedOp(name, start, dur, 0, program, "", "fusion", False,
                      scope)


def test_ungated_roofline_reader_on_hand_built_ticks(monkeypatch):
    reader = cells.load_module(REPO, BENCH, "metrics", "moe_ungated_roofline")

    def ev(name, t0, dur, tick, **args):
        return pe.Event(name, t0, dur, args={"tick": tick, **args})
    evs = [ev(pe.STEP_DECODE, 1.0, 0.2, 1, expert_rows=1920,
              experts_touched=320),
           ev(pe.DECODE_WAIT, 1.2, 0.3, 1),
           ev(pe.PREFILL_CHUNK, 2.0, 0.2, 2, expert_rows=15360,
              experts_touched=320),
           ev(pe.DECODE_WAIT, 2.3, 0.2, 2)]
    experts = "jit(decode_step_g)/moe/experts/jit(_routed_sum)/x"
    ops = [_op("grouped_matmul_relu2_in.1", 1.05, 0.006, experts),
           _op("grouped_matmul.2", 1.2, 0.006, experts),
           _op("fusion.1", 2.1, 0.008, experts),
           _op("fusion.2", 2.2, 0.5, "jit(decode_step_g)/moe/shared/dot"),
           _op("fusion.3", 0.2, 0.5, experts)]       # outside every tick
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops, [0], 0.0))
    monkeypatch.setattr(reader.pe, "events", lambda: evs)
    # 320 touched experts x 2 matrices of 2,688 x 1,856 bfloat16, twice: the
    # decode tick's 1,920 pairs are far below the bytes, the chunk's 15,360
    # (1.56 ms of operations) still under its 7.8 ms of bytes
    nbytes = 320 * 2 * 2688 * 1856 * 2
    assert reader.costs_nemotron_h.ungated_expert_bytes(320, HF, 2) == nbytes
    assert reader.costs_nemotron_h.ungated_expert_flops(15360, HF) \
        == 15360 * 4 * 2688 * 1856
    assert reader.read(_Obs(HF)) == pytest.approx(
        100 * 2 * nbytes / 819e9 / 0.020)
    # a gated family reads nothing here, and a program without the counts
    assert reader.read(_Obs({**HF, "mlp_hidden_act": "silu"})) is None
    monkeypatch.setattr(reader.pe, "events", lambda: [
        ev(pe.STEP_DECODE, 1.0, 0.2, 1), ev(pe.DECODE_WAIT, 1.2, 0.3, 1)])
    assert reader.read(_Obs(HF)) is None
    monkeypatch.setattr(reader.named_readers, "named_ops", lambda obs: None)
    assert reader.read(_Obs(HF)) is None
