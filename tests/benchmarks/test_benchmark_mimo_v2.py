"""The files ISSUE 41 adds to the benchmark, rehearsed on the CPU: the
``mimo_v2`` family and reference, a toy configuration (one chip of two: 8 of
16 experts held, 1 KV head in a full layer and 2 in a windowed one, keys of
24 beside values of 16) and traffic mix, the two new metric readers and the
cost functions beside them, through the same runner and result line as
``benchmarks/run.py`` and with no edit to a file that was there
(``benchmark_rehearsal.py``)."""

import dataclasses
import json
import shutil

import pytest

import benchmark_rehearsal as rehearsal
import test_benchmark_contract as contract
from benchmarks.harness import cells, costs_kind_pages, costs_latent
from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn

REPO = rehearsal.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL, CONFIG, MIX = "tiny-agent-long", "tiny-mimo-v2", "tiny-agent-long"
LIKE = "mimo-v2.5-serve-agent-long"
REAL_NAME = "mimo-v2.5-serve-d7-e16"
REAL = REPO / "benchmarks" / "configs" / f"{REAL_NAME}.json"
NEW_METRICS = ("kind_paged_decode_roofline", "kind_paged_prefill_roofline")
JOINED = ("kv_bytes_per_live_token", "window_attn_share", "full_attn_share",
          "idle_with_work_share", "step_starved_share", "step_ahead_share",
          "host_gc_pause_max_ms")
HF = json.loads(REAL.read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal's checkout plus the toy cell, as files and entries."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench_mimo_v2"))
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
    # the traced run switched the program's tracer on: leave the process as
    # the next test file expects it, off and with an empty ring
    get_tracer().configure(enabled=False)
    get_tracer().clear()


def test_nothing_that_was_there_is_edited(root):
    assert rehearsal.files_that_differ(root) == []


@pytest.mark.parametrize("config,chips", [
    (f"benchmarks/configs/{REAL_NAME}.json", 16),
    (f"tests/benchmarks/data/{CONFIG}.json", 2)])
def test_configuration_keeps_the_rule_and_the_floors(config, chips):
    data = json.loads((REPO / config).read_text())
    entry = {"name": "x", "file": config, "why": "x",
             "source": data["source"], "reduced": data["reduced"]}
    assert contract.configuration_faults(entry, data) == []
    assert data["deployment_chips"] == chips
    assert data["first_expert_held"] == 0


def test_configuration_is_the_catalog_row_but_for_depth_and_experts(bench):
    """Every number of the published config at its published value, except
    depth and the experts held; the two per-layer lists keep their first
    seven entries (the row is copied here: the catalog lies outside the
    repository)."""
    published = {
        "attention_bias": False, "attention_chunk_size": 128,
        "attention_value_scale": 0.707,
        "attention_projection_layout": "fused_qkv",
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
        "swa_num_attention_heads": 64, "swa_head_dim": 192,
        "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
        "hidden_size": 4096, "hybrid_block_size": None,
        "intermediate_size": 16384, "layernorm_epsilon": 1e-05,
        "max_position_embeddings": 1048576, "model_type": "mimo_v2",
        "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 256,
        "n_shared_experts": None, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "partial_rotary_factor": 0.334,
        "rope_scaling": {"rope_type": "default", "type": "default"},
        "rope_theta": 10000000, "routed_scaling_factor": None,
        "scoring_func": "sigmoid", "sliding_window": 128,
        "sliding_window_size": 128, "swa_rope_theta": 10000,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152576}
    differ = {k for k, v in published.items() if HF.get(k, "absent") != v}
    assert differ == set(HF["reduced"]) == {"num_hidden_layers",
                                            "n_routed_experts"}
    assert HF["published"] == {"num_hidden_layers": 48,
                               "n_routed_experts": 256}
    assert (HF["num_hidden_layers"], HF["n_routed_experts"]) == (7, 16)
    # the published lists' first seven: a leading dense full layer, then
    # windowed x 4, full, windowed (5 : 1)
    assert HF["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert HF["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert len(HF["assumed"]) >= 8
    for said in ("sink", "attention_value_scale", "attention_chunk_size",
                 "multi-token-prediction", "fused_qkv"):
        assert any(said in text for text in HF["assumed"]), said
    entry, = [c for c in bench["configs"] if c["name"] == REAL_NAME]
    assert entry["source"] == HF["source"]
    cell, = [w for w in bench["workloads"] if w["name"] == LIKE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (REAL_NAME, "agent-long", 1)
    assert "sixteen" in HF["deployment"] and "4,523,557,184" in \
        HF["deployment"]


def test_traffic_is_the_issues_letter_for_letter():
    from benchmarks.harness import traffic
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "agent-long.json").read_text())
    assert {k: mix[k] for k in ("kind", "loop", "clients", "max_concurrency",
                                "block_requests", "blocks", "ramp_s",
                                "drain_s")} == {
        "kind": "serve", "loop": "closed", "clients": 32,
        "max_concurrency": 32, "block_requests": 32, "blocks": 64,
        "ramp_s": 24.0, "drain_s": 20.0}
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 8192,
                                    "max": 24576}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert "order_seed" not in mix
    prompts = traffic.quantile_lengths(mix["prompt_tokens"], 32)
    outputs = traffic.quantile_lengths(mix["output_tokens"], 32)
    assert (prompts[0], prompts[-1]) == (8448, 24320)
    assert (round(prompts.mean()), round(outputs.mean())) == (16384, 256)
    # no request is more than 1.8% of a block's tokens
    assert (prompts.max() + outputs.max()) / (prompts + outputs).sum() < 0.05
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest == HF["serve"]["max_context"] == 390 * 64


def test_metrics_list_the_cell_and_move_tokens_per_second(bench):
    """Membership only: a later cell may join any of these lists, and this
    one any list it reports, with no edit here."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert LIKE in by_name[name]["workloads"]
        assert (by_name[name]["moves"], by_name[name]["layer"],
                by_name[name]["source"], by_name[name]["unit"]) == \
            ("serve_tokens_per_s", "kernels", "device_trace", "%")
    for name in JOINED:
        assert LIKE in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    tokens, = [m for m in bench["end_to_end"]
               if m["name"] == "serve_tokens_per_s"]
    assert LIKE in tokens["workloads"]


def test_family_builds_the_published_widths_and_the_held_sixteenth():
    family = cells.load_module(REPO, BENCH, "families", "mimo_v2")
    cfg, model = family.build(HF, "serve", HF["serve"])
    assert (cfg.num_layers, cfg.n_routed_experts, cfg.held, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.vocab_size, cfg.hidden_size) == \
        (7, 256, 16, 0, 8, 152576, 4096)
    assert (cfg.full.num_heads, cfg.full.num_kv_heads, cfg.full.head_dim,
            cfg.full.v_head_dim) == (64, 4, 192, 128)
    assert (cfg.swa.num_heads, cfg.swa.num_kv_heads, cfg.swa.head_dim,
            cfg.swa.v_head_dim) == (64, 8, 192, 128)
    assert [cfg.window(i) for i in range(7)] == \
        [None, 128, 128, 128, 128, None, 128]
    assert [cfg.is_dense(i) for i in range(7)] == [True] + [False] * 6
    assert [cfg.has_sink(i) for i in range(7)] == \
        [False, True, True, True, True, False, True]
    assert (cfg.rope_base(0), cfg.rope_base(1), cfg.rotary_dim(0)) == \
        (1e7, 1e4, 64)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.attention_value_scale, cfg.routed_scaling_factor) == \
        (16384, 2048, 0.707, 1.0)
    assert cfg.max_seq_len == HF["serve"]["max_context"] == 24960
    assert family.SINK_MEAN == 4.0 and family.SINK_SPREAD == 1.0
    engine = HF["serve"]["engine"]
    assert engine["ctx_block_buckets"][-1] == 390
    assert engine["kv_num_blocks"] == 32 * 390 + 1
    assert engine["scheduler"]["max_tokens_per_step"] == 4096
    assert engine["scheduler"]["max_decode_batch"] == 32
    assert len(engine["scheduler"]["prefill_buckets"]) \
        * len(engine["ctx_block_buckets"]) <= 16
    assert len(engine["decode_batch_buckets"]) \
        * len(engine["ctx_block_buckets"]) <= 8
    # the check's wave: a prompt past two chunks (and so past a release
    # behind the window), several between the window and a chunk, several
    # under the window
    others = HF["serve"]["check"]["others"]
    assert max(others) > 2 * 4096
    assert sum(128 < n < 4096 for n in others) >= 3
    assert sum(n <= 128 for n in others) >= 2
    with pytest.raises(ValueError):
        family.build(HF, "train", {})


@pytest.mark.parametrize("traced", [False, True])
def test_toy_cell_is_correct_and_compiles_nothing_in_the_window(runs, traced):
    obs, line, lines = runs[traced]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert obs.counters["compiles_in_window"] == 0
    assert any("reference check" in text and "agree" in text for text in lines)


def test_traced_run_reports_the_program_counter_metrics(runs):
    """Device-trace metrics need a TPU plane: their readers find nothing on
    the CPU, return None and are left out, as on a program without them.
    The cell's own counter is IN the line; which of the accepted counters
    read the ring beside it is theirs to say."""
    _, line, _ = runs[True]
    assert "kv_bytes_per_live_token" in line["metrics"]
    assert not any(k.endswith("_roofline") or k.endswith("_share")
                   for k in line["metrics"])
    # two full layers of 1 head cost 2 x (24 + 16) x 2 = 160 bytes a token
    # where no row is padded, and 2 x (128 + 128) x 2 = 1,024 as the toy's
    # pool stores them (rows of 128 lanes); the windowed layers' blocks
    # (5 layers x 2 heads x 512 bytes a token of a block) lie on top, over
    # short sequences
    assert 1024 < line["metrics"]["kv_bytes_per_live_token"]["value"] < 8192


def test_every_new_reader_reads_the_toy_run_without_raising(runs):
    obs, _, _ = runs[True]
    for name in NEW_METRICS + JOINED:
        reader = cells.load_module(REPO, BENCH, "metrics", name)
        value = reader.read(obs)
        assert value is None or value > 0, name


def test_ticks_carry_the_bytes_by_kind(runs):
    obs, _, _ = runs[True]
    counted = [t[2] for t in obs.ticks if t[2].get("kv_live_tokens")]
    assert counted
    for c in counted:
        assert c["kv_held_bytes"] == c["kv_full_bytes"] + c["kv_window_bytes"]
        assert c["kv_full_bytes"] == c["kv_full_blocks"] * 16 * 2 * 256 * 2
        assert c["kv_window_bytes"] == \
            c["kv_window_blocks"] * 16 * 5 * 2 * 256 * 2
    bare = [(a, b, {k: v for k, v in c.items() if not k.startswith("kv_")},
             d) for a, b, c, d in obs.ticks]
    reader = cells.load_module(REPO, BENCH, "metrics",
                               "kv_bytes_per_live_token")
    assert reader.read(dataclasses.replace(obs, ticks=bare)) is None


# --- the cost functions and the readers, by hand -------------------------------

def test_kind_page_costs_by_hand():
    assert costs_kind_pages.has_kinds(HF)
    assert not costs_kind_pages.has_kinds({"layer_types": []})
    assert costs_kind_pages.layers_by_kind(HF) == {"full": 2, "window": 5}
    assert costs_kind_pages.kind_shape(HF, "full") == {
        "heads": 64, "kv_heads": 4, "d_k": 192, "d_v": 128}
    assert costs_kind_pages.kind_shape(HF, "window")["kv_heads"] == 8
    assert costs_kind_pages.token_bytes(HF, "full", 2) == 2560
    assert costs_kind_pages.token_bytes(HF, "window", 2) == 5120
    assert costs_kind_pages.least_pool_token_bytes(HF, 2) == 5120
    # 32 sequences of 16,500 tokens: the full layers read all of them, the
    # windowed layers 128 a sequence
    assert costs_kind_pages.kind_decode_bytes(528_000, 32 * 128, HF, 2) == \
        2 * 2560 * 528_000 + 5 * 5120 * 4096
    # a chunk of 4 queries from position 2 behind a window of 4: queries at
    # 2, 3 see 3, 4 keys, those at 4, 5 the window's 4
    assert costs_kind_pages.windowed_chunk_pairs(2, 4, 4) == 3 + 4 + 4 + 4
    assert costs_kind_pages.windowed_chunk_pairs(0, 3, 128) == 1 + 2 + 3
    assert costs_kind_pages.windowed_chunk_pairs(4096, 4096, 128) == \
        4096 * 128
    assert costs_kind_pages.windowed_chunk_pairs(100, 100, 128) == \
        sum(min(t + 1, 128) for t in range(100, 200))
    # a 4,096-token chunk from 12,288: the full layers keep 12,288 x 4,096 +
    # 4,096 x 4,097 / 2 pairs, the windowed 128 a row; 64 heads, 2 x 320
    full = costs_latent.chunk_pairs(12288, 4096)
    assert costs_kind_pages.kind_prefill_flops(12288, 4096, HF) == \
        (2 * full + 5 * 4096 * 128) * 64 * 640
    # ISSUE 41's 5.5 TFLOP: the chunk whose first query stands at 14,336
    assert 5.4e12 < 2 * costs_latent.chunk_pairs(14336, 4096) * 64 * 640 \
        < 5.6e12


class _Obs:
    device_kind = "TPU v5 lite"
    window = (0.0, 100.0)

    def __init__(self, config):
        self.cell = type("Cell", (), {"config": config})()
        self.trace = type("Trace", (), {"window": (0.0, 100.0)})()


def _op(name, start, dur, scope, program="jit_decode_step_g", kernel=True):
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
    span lacks the windowed count (another program's)."""
    def ev(name, t0, dur, tick, **args):
        return pe.Event(name, t0, dur, args={"tick": tick, **args})
    return [
        ev(pe.PREFILL_CHUNK, 1.0, 0.2, 1, tokens=4096, start=8192),
        ev(pe.DECODE_WAIT, 1.2, 0.3, 1),
        ev(pe.PREFILL_CHUNK, 2.0, 0.2, 2, tokens=1000, start=0),
        ev(pe.STEP_DECODE, 2.2, 0.6, 2, ctx_tokens=500_000,
           ctx_tokens_windowed=4096),
        ev(pe.DECODE_WAIT, 2.6, 0.2, 2),
        ev(pe.STEP_DECODE, 3.0, 0.5, 3, ctx_tokens=9),
        ev(pe.DECODE_WAIT, 3.3, 0.2, 3)]


def test_decode_roofline_reader_on_hand_built_ticks(monkeypatch):
    """The kernel's calls in decode programs inside the ticks' intervals
    (from the wait before to the tick's own: a program dispatched ahead runs
    after its tick's span) took 10 ms together; a call in a chunk program,
    one outside every interval and another kernel's are left out."""
    reader = cells.load_module(REPO, BENCH, "metrics",
                               "kind_paged_decode_roofline")
    full = "jit(decode_step_g)/attn/full/attn/paged"
    window = "jit(decode_step_g)/attn/window/attn/paged"
    ops = [_op("paged_attention.3", 2.3, 0.004, full),
           # across the boundary between tick 2's interval and tick 3's
           # (2.8): the next program's first call, running before the host
           # saw the step before it end
           _op("paged_attention.3", 2.799, 0.002, full),
           _op("paged_attention.4", 2.7, 0.004, window),
           _op("paged_attention.3", 2.4, 0.5, full,
               program="jit_prefill_chunk_g"),
           _op("paged_attention.3", 0.5, 0.5, full),
           _op("grouped_matmul.1", 2.5, 0.5, "x/moe/experts")]
    _patched(monkeypatch, reader, ops, _ticks())
    need = 2 * 2560 * 500_000 + 5 * 5120 * 4096
    assert reader.read(_Obs(HF)) == pytest.approx(100 * need / 819e9 / 0.010)
    _patched(monkeypatch, reader, ops[-1:], _ticks())  # no such kernel
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, None, _ticks())      # no trace to read
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, ops, _ticks())       # another family
    assert reader.read(_Obs({"layer_types": ["full_attention"]})) is None
    _patched(monkeypatch, reader, ops, _ticks()[5:])   # the parent's spans
    assert reader.read(_Obs(HF)) is None


def test_touching_intervals_are_joined():
    assert costs_kind_pages.joined([(2.0, 3.0), (0.0, 1.0), (1.0, 1.5),
                                    (1.2, 1.4), (4.0, 5.0)]) == \
        [(0.0, 1.5), (2.0, 3.0), (4.0, 5.0)]
    assert costs_kind_pages.joined([]) == []


def test_prefill_roofline_reader_on_hand_built_ticks(monkeypatch):
    reader = cells.load_module(REPO, BENCH, "metrics",
                               "kind_paged_prefill_roofline")
    full = "jit(prefill_chunk_g)/attn/full/attn/paged"
    window = "jit(prefill_chunk_g)/attn/window/attn/paged"
    chunk = "jit_prefill_chunk_g"
    ops = [_op("paged_attention.3", 1.05, 0.060, full, program=chunk),
           _op("paged_attention.4", 1.15, 0.010, window, program=chunk),
           _op("paged_attention.3", 2.05, 0.030, full, program=chunk),
           _op("paged_attention.3", 2.3, 0.5, full),      # a decode call
           _op("paged_attention.3", 0.2, 0.5, full, program=chunk)]
    _patched(monkeypatch, reader, ops, _ticks())
    need = costs_kind_pages.kind_prefill_flops(8192, 4096, HF) \
        + costs_kind_pages.kind_prefill_flops(0, 1000, HF)
    assert reader.read(_Obs(HF)) == pytest.approx(100 * need / 197e12 / 0.100)
    _patched(monkeypatch, reader, ops[3:4], _ticks())  # no chunk call
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, None, _ticks())
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, ops, _ticks())
    assert reader.read(_Obs({"num_hidden_layers": 3})) is None
    _patched(monkeypatch, reader, ops, _ticks()[3:])   # no chunk span
    assert reader.read(_Obs(HF)) is None
