"""The files ISSUE 44 adds to the benchmark, rehearsed on the CPU: the
``granitemoehybrid`` family and reference, a toy configuration (the whole toy
on one device, four state slots) and traffic mix, the four new metric readers
and the cost functions beside them, through the same runner and result line
as ``benchmarks/run.py`` and with no edit to a file that was there
(``benchmark_rehearsal.py``). What a test says of ``BENCHMARK.json`` it says
by membership (``benchmarks/README.md``)."""

import dataclasses
import json
import shutil

import pytest

import benchmark_rehearsal as rehearsal
import test_benchmark_contract as contract
from benchmarks.harness import cells, costs_ssm
from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn

REPO = rehearsal.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL, CONFIG, MIX = "tiny-chat-many", "tiny-granite-hybrid", "tiny-chat-many"
LIKE = "granite4h-micro-serve-chat-many"
REAL_NAME = "granite-4.0-h-micro-serve"
REAL = REPO / "benchmarks" / "configs" / f"{REAL_NAME}.json"
NEW_METRICS = ("ssm_update_roofline", "ssm_scan_roofline", "ssm_share",
               "state_slots_fill")
JOINED = ("prefill_tokens_per_tick", "prefill_idle_attributed_share",
          "prefill_bucket_fill", "idle_with_work_share", "step_starved_share",
          "step_ahead_share", "host_gc_pause_max_ms", "full_attn_share",
          "kv_bytes_per_live_token")
HF = json.loads(REAL.read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal's checkout plus the toy cell, as files and entries."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench_granite"))
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
def test_configuration_keeps_the_rule_and_is_cut_nowhere(config):
    data = json.loads((REPO / config).read_text())
    entry = {"name": "x", "file": config, "why": "x",
             "source": data["source"], "reduced": data["reduced"]}
    assert contract.configuration_faults(entry, data) == []
    assert data["reduced"] == [] and data["published"] == {}
    assert "deployment_chips" not in data


def test_configuration_is_the_catalog_row(bench):
    """Every key of the published config at its published value, none cut
    (the row is copied here: the catalog lies outside the repository)."""
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "layer_types": period * 4, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    assert {k for k, v in published.items()
            if HF.get(k, "absent") != v} == set()
    assert len(HF["layer_types"]) == 40 and HF["vocab_size"] == 100352
    assert len(HF["assumed"]) >= 8
    for said in ("A_log", "softplus", "silu(z) BEFORE", "float32",
                 "head_dim 64", "nope", "Multi-token"):
        assert any(said.lower() in text.lower() for text in HF["assumed"]), \
            said
    entry, = [c for c in bench["configs"] if c["name"] == REAL_NAME]
    assert entry["source"] == HF["source"] and entry["reduced"] == []
    assert entry["file"] == f"benchmarks/configs/{REAL_NAME}.json"
    cell, = [w for w in bench["workloads"] if w["name"] == LIKE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (REAL_NAME, "chat-many", 1)
    assert "3,191,396,096" in HF["deployment"] \
        and "76,437,504" in HF["deployment"]


def test_traffic_is_the_issues_letter_for_letter():
    from benchmarks.harness import traffic
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "chat-many.json").read_text())
    assert {k: mix[k] for k in ("kind", "loop", "clients", "max_concurrency",
                                "block_requests")} == {
        "kind": "serve", "loop": "closed", "clients": 64,
        "max_concurrency": 64, "block_requests": 64}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.8, "min": 64, "max": 2048}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.7, "min": 64, "max": 1024}
    assert "order_seed" not in mix and "rate_rps" not in mix
    chat = json.loads((REPO / "benchmarks" / "traffic" /
                       "chat.json").read_text())
    assert mix["prompt_tokens"] == chat["prompt_tokens"]
    prompts = traffic.quantile_lengths(mix["prompt_tokens"], 64)
    outputs = traffic.quantile_lengths(mix["output_tokens"], 64)
    assert 600 < prompts.mean() < 800 and 400 < outputs.mean() < 520
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest == 48 * 64 <= HF["serve"]["max_context"]
    # no caller runs out: the blocks hold twice what the chip's peaks allow
    # in ramp and window (6,000 tokens/s over 71 s)
    served = mix["blocks"] * mix["block_requests"] \
        * (prompts.mean() + outputs.mean())
    assert served > 2 * 6000 * (mix["ramp_s"] + 51)


def test_metrics_list_the_cell_and_move_tokens_per_second(bench):
    """Membership only: a later cell may join any of these lists, and this
    one any list it reports, with no edit here."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    want = {"ssm_update_roofline": ("kernels", "device_trace", "%"),
            "ssm_scan_roofline": ("kernels", "device_trace", "%"),
            "ssm_share": ("model step", "device_trace", "%"),
            "state_slots_fill": ("cache manager", "program_counter", "%")}
    for name, (layer, source, unit) in want.items():
        assert LIKE in by_name[name]["workloads"]
        assert (by_name[name]["moves"], by_name[name]["layer"],
                by_name[name]["source"], by_name[name]["unit"]) == \
            ("serve_tokens_per_s", layer, source, unit)
    for name in JOINED:
        assert LIKE in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    tokens, = [m for m in bench["end_to_end"]
               if m["name"] == "serve_tokens_per_s"]
    assert LIKE in tokens["workloads"]


def test_family_builds_the_published_widths_whole():
    family = cells.load_module(REPO, BENCH, "families", "granitemoehybrid")
    cfg, model = family.build(HF, "serve", HF["serve"])
    assert (cfg.num_layers, cfg.vocab_size, cfg.hidden_size,
            cfg.intermediate_size) == (40, 100352, 2048, 8192)
    assert [i for i in range(40) if not cfg.is_mamba(i)] == [5, 15, 25, 35]
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.kv_pack) == \
        (32, 8, 64, 2)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state,
            cfg.mamba_d_conv, cfg.mamba_chunk_size, cfg.d_inner,
            cfg.conv_channels, cfg.in_proj_width) == \
        (64, 64, 128, 4, 256, 4096, 4352, 8512)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == \
        (12.0, 0.22, 0.015625, 8.0)
    assert cfg.max_seq_len == HF["serve"]["max_context"] == 4096
    engine = HF["serve"]["engine"]
    assert engine["max_tracked_sequences"] == 64 \
        == engine["scheduler"]["max_decode_batch"]
    assert engine["kv_num_blocks"] == 64 * 48 + 1
    assert len(engine["scheduler"]["prefill_buckets"]) \
        * len(engine["ctx_block_buckets"]) \
        + len(engine["decode_batch_buckets"]) \
        * len(engine["ctx_block_buckets"]) <= 12
    # the check crosses a chunk boundary with a state in hand
    check = HF["serve"]["check"]
    assert check["prompt"] > max(engine["scheduler"]["prefill_buckets"])
    assert check["prompt"] % 256 and check["others"][:3] == [100, 300, 700]
    # ... then decoded through the state for as long as the context ladder
    # lets it: a state re-rounded every token drifts by the decoded tokens'
    # count (serve.logit_margin_why); one prompt short enough to feel a
    # slot's last holder
    assert min(check["others"]) <= 32 and check["new_tokens"] >= 1000
    assert check["prompt"] + check["new_tokens"] \
        <= 64 * max(engine["ctx_block_buckets"]) <= HF["serve"]["max_context"]
    # what the cache holds: the issue's bytes
    from deepspeed_tpu.inference.v2.modules import policy_for
    spec = policy_for(cfg).cache_spec(cfg)
    assert spec.layer_kinds.count("state") == 36
    assert 36 * spec.state_slot.layer_bytes(2) == 76_437_504
    assert spec.state_slot.stored == (32, 128, 128)
    assert (spec.num_kv_heads, spec.head_dim) == (4, 128)
    with pytest.raises(ValueError):
        family.build(HF, "train", {})
    with pytest.raises(ValueError, match="routed experts"):
        family.build({**HF, "num_local_experts": 64}, "serve", HF["serve"])


def test_family_draws_a_quiet_embedding_and_heads_that_remember():
    """The benchmark's weights are the program's own draw but for the
    embedding (1 / embedding_multiplier as large) and the Mamba-2 heads'
    ``a_log`` and ``dt_bias`` (the long end of their initialisers' ranges);
    the configuration's ``assumed`` says both."""
    import jax
    import numpy as np
    from deepspeed_tpu.models import granite_hybrid as gh
    family = cells.load_module(REPO, BENCH, "families", "granitemoehybrid")
    toy = json.loads((rehearsal.DATA / f"{CONFIG}.json").read_text())
    cfg, model = family.build(toy, "serve", toy["serve"])
    example = {"input_ids": np.zeros((1, 8), np.int32)}
    key = jax.random.PRNGKey(3)
    got = model.init(key, example)["params"]
    own = gh.GraniteHybridForCausalLM(cfg).init(key, example)["params"]
    np.testing.assert_allclose(
        got["embed"]["embedding"],
        own["embed"]["embedding"] / cfg.embedding_multiplier)
    mamba = [n for n in own if "mamba" in own[n]]
    assert len(mamba) == sum(cfg.is_mamba(i) for i in range(cfg.num_layers))
    for name in mamba:
        mp = got[name]["mamba"]
        assert np.all(np.exp(mp["a_log"]) == gh.A_RANGE[0])
        np.testing.assert_allclose(jax.nn.softplus(mp["dt_bias"]),
                                   gh.DT_MIN, rtol=1e-4)
        # nothing else of the layer moves
        for other in set(mp) - {"a_log", "dt_bias"}:
            np.testing.assert_array_equal(mp[other], own[name]["mamba"][other])
        np.testing.assert_array_equal(got[name]["mlp"]["w_up"]["kernel"],
                                      own[name]["mlp"]["w_up"]["kernel"])
    for said in ("_QuietEmbedding", "_LongMemory"):
        assert any(said in text for text in HF["assumed"]), said


def test_controls_break_the_program_where_they_say_at_toy_widths():
    """``benchmarks/controls/granitemoehybrid.py`` patches the state kind's
    own methods: the sound program agrees, ``D x`` left out and the tail a
    row early differ, through the harness's own comparison, and nothing
    stays patched. (A bfloat16 state needs the published widths, a long
    decode and the chip to show: ``serve.logit_margin_why``.)"""
    from deepspeed_tpu.inference.v2 import kv_cache
    controls = cells.load_module(REPO, BENCH, "controls", "granitemoehybrid")
    toy = json.loads((rehearsal.DATA / f"{CONFIG}.json").read_text())
    toy["serve"] = {**toy["serve"], "logit_margin_tol": 0.01,
                    "check": {**toy["serve"]["check"], "new_tokens": 24}}
    before = (kv_cache._StateSlots.attend_chunk,
              kv_cache._StateSlots.attend_decode, kv_cache.ssm.causal_conv)
    lines = []
    names = ("sound", "fp8_matrices", "no_skip", "tail_off_by_one")
    held = controls.run(toy, BENCH, 7, names, lines.append)
    assert held["sound"] and not held["no_skip"] \
        and not held["tail_off_by_one"]
    said = [json.loads(line) for line in lines]
    assert tuple(one["control"] for one in said) == names
    # the reference through float8 is further from the same tokens (how far
    # is the chip's to say: the toy has four state layers)
    margin = {one["control"]: float(
        one["line"].split("token) ")[1].split(",")[0]) for one in said}
    assert margin["fp8_matrices"] > 3 * margin["sound"]
    assert before == (kv_cache._StateSlots.attend_chunk,
                      kv_cache._StateSlots.attend_decode,
                      kv_cache.ssm.causal_conv)
    assert set(controls.CONTROLS) >= {"bf16_state", "no_carry", "no_zero"}


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
    # one attention layer of 2 KV heads of 64 = one packed row of 128 lanes:
    # 2 x 128 x 2 bytes a token in bfloat16, and the blocks' rounding on top
    assert 512 <= line["metrics"]["kv_bytes_per_live_token"]["value"] < 1024
    assert not any(k.endswith("_roofline") or k == "ssm_share"
                   for k in line["metrics"])


def test_every_new_reader_reads_the_toy_run_without_raising(runs):
    obs, _, _ = runs[True]
    for name in NEW_METRICS + JOINED:
        reader = cells.load_module(REPO, BENCH, "metrics", name)
        value = reader.read(obs)
        assert value is None or value > 0, name


def test_ticks_carry_the_slots_and_a_program_without_them_reads_nothing(runs):
    obs, _, _ = runs[True]
    counted = [t[2] for t in obs.ticks if "state_slots_held" in t[2]]
    assert counted
    for c in counted:
        assert 0 <= c["state_slots_held"] <= 4
        # 4 state layers x (8 x 8 x 16 x 4 + 3 x 96 x 2) bytes a sequence
        assert c["kv_state_bytes"] == c["state_slots_held"] * 4 * 4672
    bare = [(a, b, {k: v for k, v in c.items()
                    if k not in ("state_slots_held", "kv_state_bytes")}, d)
            for a, b, c, d in obs.ticks]
    reader = cells.load_module(REPO, BENCH, "metrics", "state_slots_fill")
    assert reader.read(dataclasses.replace(obs, ticks=bare)) is None


# --- the readers, on hand-built ticks ----------------------------------------

class _Obs:
    device_kind = "TPU v5 lite"
    window = (0.0, 100.0)

    def __init__(self, config):
        self.cell = type("Cell", (), {"config": config})()
        self.trace = type("Trace", (), {"window": (0.0, 100.0)})()


def _op(name, start, dur, scope, program="jit_decode_step_g", kernel=False):
    return xn.NamedOp(name, start, dur, 0, program,
                      'custom_call_target="tpu_custom_call"' if kernel else "",
                      "custom-call" if kernel else "fusion", False, scope)


def _patched(monkeypatch, reader, ops, evs):
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: None if ops is None else (ops, [0], 0.0))
    monkeypatch.setattr(reader.pe, "events", lambda: evs)


def _ticks():
    """Three ticks that each end in a wait: tick 1 a chunk that ends a
    prompt, tick 2 a chunk and a decode batch, tick 3 a decode batch whose
    span lacks the batch (another program's)."""
    def ev(name, t0, dur, tick, **args):
        return pe.Event(name, t0, dur, args={"tick": tick, **args})
    return [
        ev(pe.PREFILL_CHUNK, 1.0, 0.2, 1, tokens=452, start=2048),
        ev(pe.DECODE_WAIT, 1.2, 0.3, 1),
        ev(pe.PREFILL_CHUNK, 2.0, 0.2, 2, tokens=1000, start=0),
        ev(pe.STEP_DECODE, 2.2, 0.6, 2, batch=60, bucket=64),
        ev(pe.DECODE_WAIT, 2.6, 0.2, 2),
        ev(pe.STEP_DECODE, 3.0, 0.5, 3),
        ev(pe.DECODE_WAIT, 3.3, 0.2, 3)]


def test_update_roofline_reader_on_hand_built_ticks(monkeypatch):
    """The operations under ``ssm/update`` and ``ssm/conv`` in decode
    programs that ended inside the ticks' intervals took 12 ms together,
    kernel or not; one in a chunk program, one outside every interval and
    another scope's are left out."""
    reader = cells.load_module(REPO, BENCH, "metrics", "ssm_update_roofline")
    update = "jit(decode_step_g)/ssm/update/pallas_call"
    ops = [_op("ssm_update.3", 2.3, 0.004, update, kernel=True),
           # across the boundary between tick 2's interval and tick 3's
           _op("ssm_update.3", 2.799, 0.002, update, kernel=True),
           _op("fusion.9", 2.7, 0.004, "jit(decode_step_g)/ssm/update/mul"),
           _op("fusion.7", 2.4, 0.5, update, program="jit_prefill_chunk_g"),
           _op("ssm_update.3", 0.5, 0.5, update, kernel=True),
           # the tails' shift counts with the update, the gated norm not
           _op("fusion.8", 2.5, 0.002, "jit(decode_step_g)/ssm/conv/add"),
           _op("fusion.6", 2.5, 0.5, "jit(decode_step_g)/ssm/norm/mul")]
    _patched(monkeypatch, reader, ops, _ticks())
    need = 60 * 36 * 2 * (2_097_152 + 26_112)
    assert reader.read(_Obs(HF)) == pytest.approx(100 * need / 819e9 / 0.012)
    _patched(monkeypatch, reader, ops[-1:], _ticks())  # no such scope
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, None, _ticks())      # no trace to read
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, ops, _ticks())       # another family
    assert reader.read(_Obs({"layer_types": ["full_attention"]})) is None
    _patched(monkeypatch, reader, ops, _ticks()[5:])   # the parent's spans
    assert reader.read(_Obs(HF)) is None


def test_scan_roofline_reader_on_hand_built_ticks(monkeypatch):
    reader = cells.load_module(REPO, BENCH, "metrics", "ssm_scan_roofline")
    scan = "jit(prefill_chunk_g)/ssm/scan/dot_general"
    chunk = "jit_prefill_chunk_g"
    ops = [_op("fusion.3", 1.05, 0.060, scan, program=chunk),
           _op("fusion.4", 2.05, 0.040, scan, program=chunk),
           _op("fusion.3", 2.3, 0.5, scan),                # a decode program
           _op("fusion.5", 2.1, 0.5, "x/ssm/conv/y", program=chunk),
           _op("fusion.3", 0.2, 0.5, scan, program=chunk)]
    _patched(monkeypatch, reader, ops, _ticks())
    # 1,452 tokens in two chunks: the rows' bytes and two states in and out
    # (0.94 ms) outlast the operations (0.70 ms at the bf16 peak)
    flops = 1452 * 36 * 5 * 4096 * 128
    nbytes = 36 * (1452 * (4352 + 64 + 4096) * 2 + 2 * 2 * 2_097_152)
    assert costs_ssm.scan_flops(1452, HF) == flops
    assert costs_ssm.scan_bytes(2, 1452, HF, 2) == nbytes
    assert nbytes / 819e9 > flops / 197e12
    assert reader.read(_Obs(HF)) == pytest.approx(
        100 * nbytes / 819e9 / 0.100)
    _patched(monkeypatch, reader, ops[2:3], _ticks())  # no chunk operation
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, None, _ticks())
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, ops, _ticks())
    assert reader.read(_Obs({"num_hidden_layers": 3})) is None
    _patched(monkeypatch, reader, ops, _ticks()[3:])   # no chunk span
    assert reader.read(_Obs(HF)) is None


def test_share_reader_counts_the_four_scopes_and_not_the_projections(
        monkeypatch):
    reader = cells.load_module(REPO, BENCH, "metrics", "ssm_share")
    ops = [_op("a", 1.0, 0.010, "p/ssm/conv/x"),
           _op("b", 1.1, 0.020, "p/ssm/scan/x"),
           _op("c", 1.2, 0.030, "p/ssm/update/x", kernel=True),
           _op("d", 1.3, 0.040, "p/ssm/norm/x"),
           _op("e", 1.4, 0.500, "p/ssm/in_proj/x"),
           _op("f", 1.9, 0.400, "p/ssm/out_proj/x"),
           _op("g", 2.3, 0.500, "p/mlp/x")]
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops, [0], 0.0))
    monkeypatch.setattr(reader.tr, "busy_seconds", lambda trace, window: 2.0)
    assert reader.read(_Obs(HF)) == pytest.approx(100 * 0.100 / 2.0)
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: (ops[4:], [0], 0.0))
    assert reader.read(_Obs(HF)) is None
