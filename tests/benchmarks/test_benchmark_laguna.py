"""The files ISSUE 35 adds to the benchmark, rehearsed on the CPU: the
``laguna`` family and reference, a toy configuration (one chip of two: 8 of
16 experts held) and traffic mix, the four new metric readers and the cost
functions beside them, through the same runner and result line as
``benchmarks/run.py`` and with no edit to a file that was there
(``benchmark_rehearsal.py``)."""

import dataclasses
import json
import shutil

import pytest

import benchmark_rehearsal as rehearsal
import test_benchmark_contract as contract
from benchmarks.harness import cells, costs_mixed_pages
from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn

REPO = rehearsal.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL, CONFIG, MIX = "tiny-code-mixed", "tiny-laguna", "tiny-code-mixed"
LIKE = "laguna-s-serve-code-mixed"
REAL_NAME = "laguna-s-2.1-serve-d5-e128"
REAL = REPO / "benchmarks" / "configs" / f"{REAL_NAME}.json"
NEW_METRICS = ("kv_bytes_per_live_token", "window_attn_share",
               "full_attn_share", "mixed_paged_roofline")
HF = json.loads(REAL.read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal's checkout plus the toy cell, as files and entries."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench_laguna"))
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
    out = {}
    for traced in (False, True):
        lines = []
        obs, line = rehearsal.run_cell(root, CELL, 2.0, traced, lines=lines)
        out[traced] = (obs, line, lines)
    return out


def test_nothing_that_was_there_is_edited(root):
    assert rehearsal.files_that_differ(root) == []


@pytest.mark.parametrize("config", [
    f"benchmarks/configs/{REAL_NAME}.json",
    f"tests/benchmarks/data/{CONFIG}.json"])
def test_configuration_keeps_the_rule_and_the_floors(config):
    data = json.loads((REPO / config).read_text())
    entry = {"name": "x", "file": config, "why": "x",
             "source": data["source"], "reduced": data["reduced"]}
    assert contract.configuration_faults(entry, data) == []
    assert data["deployment_chips"] == 2 and data["first_expert_held"] == 0


def test_configuration_is_the_catalog_row_but_for_depth_and_experts(bench):
    """Every number of the published config at its published value, except
    depth and the experts held; the per-layer lists keep their first five
    entries (the row is copied here: the catalog lies outside the
    repository)."""
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0}
    differ = {k for k, v in published.items() if HF.get(k, "absent") != v}
    assert differ == set(HF["reduced"]) == {"num_hidden_layers",
                                            "num_experts"}
    assert HF["published"] == {"num_hidden_layers": 48, "num_experts": 256}
    assert (HF["num_hidden_layers"], HF["num_experts"]) == (5, 128)
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert HF["layer_types"] == period + ["full_attention"]
    assert HF["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert HF["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert HF["gating_types"] == ["per_head"] * 5
    assert len(HF["assumed"]) >= 5
    entry, = [c for c in bench["configs"] if c["name"] == REAL_NAME]
    assert entry["source"] == HF["source"]
    cell, = [w for w in bench["workloads"] if w["name"] == LIKE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (REAL_NAME, "code-mixed", 1)


def test_traffic_is_the_issues_letter_for_letter():
    from benchmarks.harness import traffic
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "code-mixed.json").read_text())
    assert {k: mix[k] for k in ("loop", "clients", "max_concurrency",
                                "block_requests", "ramp_s", "drain_s")} == {
        "loop": "closed", "clients": 32, "max_concurrency": 32,
        "block_requests": 32, "ramp_s": 16.0, "drain_s": 20.0}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 3072,
                                    "sigma": 0.9, "min": 256, "max": 16384}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 64, "max": 256}
    assert "order_seed" not in mix
    prompts = traffic.quantile_lengths(mix["prompt_tokens"], 32)
    assert (prompts[0], prompts[-1]) == (442, 16384)
    assert round(prompts.mean()) == 4338
    assert 0.69 < prompts[prompts >= 4096].sum() / prompts.sum() < 0.71


def test_new_metrics_list_the_cell_and_move_tokens_per_second(bench):
    """Membership only: a later cell may join any of these lists, and this
    one any list it reports, with no edit here."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert LIKE in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    assert by_name["kv_bytes_per_live_token"]["layer"] == "cache manager"
    assert by_name["mixed_paged_roofline"]["layer"] == "kernels"
    tokens, = [m for m in bench["end_to_end"]
               if m["name"] == "serve_tokens_per_s"]
    assert LIKE in tokens["workloads"]


def test_family_builds_the_published_widths_and_the_held_half():
    family = cells.load_module(REPO, BENCH, "families", "laguna")
    cfg, _ = family.build(HF, "serve", HF["serve"])
    assert (cfg.num_layers, cfg.num_experts, cfg.held, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.vocab_size) == \
        (5, 256, 128, 0, 10, 100352)
    assert cfg.heads_per_layer == (48, 72, 72, 72, 48)
    assert [cfg.window(i) for i in range(5)] == [None, 512, 512, 512, None]
    assert cfg.rope(0).yarn.factor == 128.0
    assert cfg.max_seq_len == HF["serve"]["max_context"] == 16640
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "code-mixed.json").read_text())
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= cfg.max_seq_len
    assert HF["serve"]["engine"]["ctx_block_buckets"][-1] * 64 >= longest
    # the check's wave: a prompt past a chunk and a window, several between
    # the window and a chunk, several under the window
    others = HF["serve"]["check"]["others"]
    assert max(others) > 4608
    assert sum(512 < n < 4096 for n in others) >= 3
    assert sum(n < 512 for n in others) >= 3
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
    # two full layers of 2 x 16 in bfloat16 cost 256 bytes a token, all five
    # 640: the toy's sequences are short against its window of 32
    assert 256 < line["metrics"]["kv_bytes_per_live_token"]["value"] < 640


def test_every_new_reader_reads_the_toy_run_without_raising(runs):
    obs, _, _ = runs[True]
    for name in NEW_METRICS:
        reader = cells.load_module(REPO, BENCH, "metrics", name)
        value = reader.read(obs)
        assert value is None or value > 0, name


def test_counter_reader_finds_nothing_where_no_tick_counts(runs):
    """What a program without the counters gives: None."""
    reader = cells.load_module(REPO, BENCH, "metrics",
                               "kv_bytes_per_live_token")
    obs, _, _ = runs[True]
    assert reader.read(obs) > 0
    bare = [(a, b, {k: v for k, v in c.items() if not k.startswith("kv_")},
             d) for a, b, c, d in obs.ticks]
    assert reader.read(dataclasses.replace(obs, ticks=bare)) is None


# --- the cost functions and the readers, by hand -------------------------------

def test_mixed_page_costs_by_hand():
    assert costs_mixed_pages.layers_by_kind(HF) == {
        "full_attention": 2, "sliding_attention": 3}
    assert costs_mixed_pages.uniform_pool_token_bytes(HF, 2) == 20480
    assert costs_mixed_pages.least_pool_token_bytes(HF, 2) == 8192
    # 32 sequences of 4,500 tokens: the full layers read all of them, the
    # windowed layers 512 a sequence
    assert costs_mixed_pages.mixed_decode_bytes(144_000, 32 * 512, HF, 2) == \
        2 * 4096 * 144_000 + 3 * 4096 * 16_384


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
    if hasattr(reader, "pe"):
        monkeypatch.setattr(reader.pe, "events", lambda: evs)


def test_mixed_roofline_reader_on_hand_built_ticks(monkeypatch):
    """Two decode ticks, each ended by its wait; the kernel's calls that
    ended between the waits (full and windowed layers' alike) took 10 ms
    together; a call in a prefill program, one after the last wait and
    another kernel's are left out. (A step in flight:
    ``test_benchmark_decode_rooflines.py``.)"""
    reader = cells.load_module(REPO, BENCH, "metrics", "mixed_paged_roofline")

    def tick(n, start, **counts):
        return [pe.Event(pe.STEP_DECODE, start, 1.0,
                         args=dict(counts, tick=n)),
                pe.Event(pe.DECODE_WAIT, start + 0.9, 0.1, args={"tick": n})]
    ticks = (tick(1, 1.0, ctx_tokens=100_000, ctx_tokens_windowed=16_384)
             + tick(2, 3.0, ctx_tokens=50_000, ctx_tokens_windowed=10_000)
             + tick(3, 5.0, ctx_tokens=9))
    full = "jit(decode_step_g)/attn/full/attn/paged"
    window = "jit(decode_step_g)/attn/window/attn/paged"
    ops = [_op("paged_attention.3", 1.1, 0.004, full),
           _op("paged_attention.4", 1.2, 0.001, window),
           _op("paged_attention.3", 3.1, 0.005, full),
           _op("paged_attention.3", 3.5, 0.5, full,
               program="jit_prefill_chunk_g"),
           _op("paged_attention.3", 7.0, 0.5, full),
           _op("grouped_matmul.1", 3.6, 0.5, "x/moe/experts")]
    _patched(monkeypatch, reader, ops, ticks)
    need = 4096 * (2 * 150_000 + 3 * 26_384)
    assert reader.read(_Obs(HF)) == pytest.approx(
        100 * need / 819e9 / 0.010)
    _patched(monkeypatch, reader, ops[-1:], ticks)     # no such kernel
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, None, ticks)         # no trace to read
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, ops, ticks)          # another family
    assert reader.read(_Obs({"num_hidden_layers": 3})) is None
    _patched(monkeypatch, reader, ops, ticks[4:])      # the parent's spans
    assert reader.read(_Obs(HF)) is None


@pytest.mark.parametrize("kind", ["window", "full"])
def test_share_readers_on_hand_built_operations(monkeypatch, kind):
    reader = cells.load_module(REPO, BENCH, "metrics", f"{kind}_attn_share")
    other = {"window": "full", "full": "window"}[kind]
    ops = [_op("fusion.1", 1.0, 0.15,
               f"jit(decode_step_g)/attn/{kind}/attn/kv_write/scatter",
               kernel=False),
           _op("paged_attention.3", 1.0, 0.05,
               f"jit(prefill_chunk_g)/attn/{kind}/attn/paged/x"),
           _op("paged_attention.4", 1.0, 0.5,
               f"jit(decode_step_g)/attn/{other}/attn/paged/x"),
           _op("fusion.2", 1.0, 0.5, "jit(decode_step_g)/attn/out/dot",
               kernel=False)]
    _patched(monkeypatch, reader, ops, [])
    monkeypatch.setattr(reader.tr, "busy_seconds", lambda trace, window: 2.0)
    assert reader.read(_Obs({})) == pytest.approx(10.0)
    _patched(monkeypatch, reader, ops[2:], [])         # no such scope
    assert reader.read(_Obs({})) is None
    _patched(monkeypatch, reader, None, [])
    assert reader.read(_Obs({})) is None
