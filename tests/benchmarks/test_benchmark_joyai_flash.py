"""The files ISSUE 31 adds to the benchmark, rehearsed on the CPU: the
``joyai_llm_flash`` family and reference, a toy configuration and traffic
mix, the five new metric readers and the cost functions beside them, through
the same runner and result line as ``benchmarks/run.py`` and with no edit to
a file that was there (``benchmark_rehearsal.py``)."""

import dataclasses
import json
import shutil
import time

import pytest

import benchmark_rehearsal as rehearsal
import test_benchmark_contract as contract
from benchmarks.harness import cells, costs_latent
from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn

REPO = rehearsal.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL, CONFIG, MIX = "tiny-docqa", "tiny-joyai-flash", "tiny-docqa"
LIKE = "joyai-flash-serve-docqa"
REAL = REPO / "benchmarks" / "configs" / "joyai-llm-flash-serve-d5.json"
NEW_METRICS = ("latent_paged_roofline", "latent_prefill_roofline",
               "latent_attn_share", "moe_grouped_roofline",
               "moe_rows_per_touched_expert", "moe_grouped_share")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal's checkout plus the toy cell, as files and entries."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench_joyai"))
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
    "benchmarks/configs/joyai-llm-flash-serve-d5.json",
    f"tests/benchmarks/data/{CONFIG}.json"])
def test_configuration_keeps_the_rule_and_the_floors(config):
    data = json.loads((REPO / config).read_text())
    entry = {"name": "x", "file": config, "why": "x",
             "source": data["source"], "reduced": data["reduced"]}
    assert contract.configuration_faults(entry, data) == []


def test_configuration_is_the_catalog_row_but_for_depth(bench):
    """Every key of the published config at its published value, except
    depth (the row is copied here: the catalog lies outside the
    repository)."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    data = json.loads(REAL.read_text())
    differ = {k for k, v in published.items() if data.get(k, "absent") != v}
    assert differ == set(data["reduced"]) == {"num_hidden_layers"}
    assert data["published"] == {"num_hidden_layers": 40}
    assert data["num_hidden_layers"] == 5
    entry, = [c for c in bench["configs"]
              if c["name"] == "joyai-llm-flash-serve-d5"]
    assert entry["source"] == data["source"]
    cell, = [w for w in bench["workloads"] if w["name"] == LIKE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("joyai-llm-flash-serve-d5", "doc-qa", 1)


def test_traffic_is_the_issues_letter_for_letter():
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "doc-qa.json").read_text())
    assert {k: mix[k] for k in ("loop", "clients", "max_concurrency",
                                "block_requests", "blocks", "pairing_seed",
                                "ramp_s", "drain_s")} == {
        "loop": "closed", "clients": 32, "max_concurrency": 32,
        "block_requests": 32, "blocks": 64, "pairing_seed": 20260928,
        "ramp_s": 16.0, "drain_s": 20.0}
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 4096,
                                    "max": 8192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 64, "max": 192}
    assert "order_seed" not in mix


def test_new_metrics_list_the_cell_and_move_tokens_per_second(bench):
    """Membership only: a later cell may join any of these lists, and this
    one any list it reports, with no edit here. (Why this cell reports
    ``moe_grouped_share`` and not ``moe_expert_share`` is in that reader's
    docstring.)"""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS + ("prefill_tokens_per_tick",
                               "prefill_bucket_fill",
                               "prefill_idle_attributed_share"):
        assert LIKE in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "serve_tokens_per_s"


def test_family_bounds_the_context_and_builds_the_published_widths():
    family = cells.load_module(REPO, BENCH, "families", "joyai_llm_flash")
    data = json.loads(REAL.read_text())
    cfg, _ = family.build(data, "serve", data["serve"])
    assert (cfg.num_layers, cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.latent_dim, cfg.vocab_size) == (5, 256, 8, 576, 129280)
    assert cfg.max_seq_len == data["serve"]["max_context"] == 8448
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "doc-qa.json").read_text())
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= cfg.max_seq_len
    assert data["serve"]["engine"]["ctx_block_buckets"][-1] * 64 >= longest
    with pytest.raises(ValueError):
        family.build(data, "train", {})


@pytest.mark.parametrize("traced", [False, True])
def test_toy_cell_is_correct_and_compiles_nothing_in_the_window(runs, traced):
    obs, line, lines = runs[traced]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert obs.counters["compiles_in_window"] == 0
    assert any("reference check" in text and "agree" in text for text in lines)


def test_untraced_run_reports_tokens_per_second(runs):
    _, line, _ = runs[False]
    assert {"serve_tokens_per_s", "setup_s"} <= set(line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_run_reports_the_program_counter_metrics(runs):
    """Device-trace metrics need a TPU plane: their readers find nothing on
    the CPU, return None and are left out, as on a program without them."""
    _, line, _ = runs[True]
    assert {"prefill_tokens_per_tick", "prefill_bucket_fill",
            "moe_rows_per_touched_expert"} <= set(line["metrics"])
    assert not any(k.endswith("_roofline") or k.endswith("_share")
                   for k in line["metrics"])
    rows = line["metrics"]["moe_rows_per_touched_expert"]["value"]
    # a decode batch of 1-4 gives 1-1.5 rows an expert, a 96-token chunk
    # over 16 experts top-4 gives 24
    assert 1.0 <= rows <= 24.0


def test_every_new_reader_reads_the_toy_run_without_raising(runs):
    obs, _, _ = runs[True]
    for name in NEW_METRICS:
        reader = cells.load_module(REPO, BENCH, "metrics", name)
        value = reader.read(obs)
        assert value is None or value > 0, name


def test_counter_reader_finds_nothing_where_no_span_counts(runs):
    """What the parent commit gives, whose spans carry no such args: a
    window that holds none reads None."""
    reader = cells.load_module(REPO, BENCH, "metrics",
                               "moe_rows_per_touched_expert")
    obs, _, _ = runs[True]
    assert reader.read(obs) > 0
    assert reader.read(dataclasses.replace(obs, window=(-2.0, -1.0))) is None


# --- the cost functions, by hand ---------------------------------------------

HF = json.loads(REAL.read_text())


def test_latent_costs_by_hand():
    assert costs_latent.latent_row_bytes(HF, 2) == 1152
    assert costs_latent.latent_decode_bytes(1000, 1152) == 1_152_000
    # queries at positions 100..103 see 101 + 102 + 103 + 104 keys
    assert costs_latent.chunk_pairs(100, 4) == 410
    assert costs_latent.chunk_pairs(0, 2048) == 2048 * 2049 // 2
    # a pair a head: a 192-wide score and a 128-wide value sum
    assert costs_latent.latent_prefill_flops(1, HF) == 2 * (192 + 128) * 32
    # a row through an expert: three matmuls of 2048 x 768
    assert costs_latent.grouped_expert_flops(1, HF) == 6 * 2048 * 768
    assert costs_latent.grouped_expert_bytes(1, HF, 2) == 9_437_184
    # a 2,048-token chunk over one layer's 256 experts: 0.155 TFLOP in 0.79
    # ms against 2.4 GB in 2.95 ms: the weights' reading bounds it
    rows = 2048 * 8
    assert costs_latent.grouped_least_seconds(
        rows, 256, HF, 2, 197e12, 819e9) == pytest.approx(
            256 * 9_437_184 / 819e9)
    # the same rows on 8 experts would be bound by the matmuls
    assert costs_latent.grouped_least_seconds(
        rows, 8, HF, 2, 197e12, 819e9) == pytest.approx(
            rows * 6 * 2048 * 768 / 197e12)


def _tick(n, start, wait_end, chunk=None, decode=None, unit=0.01):
    """The spans of tick ``n``: an optional chunk, an optional decode batch
    two ``unit`` later, and the wait that ends it."""
    evs = []
    if chunk:
        evs.append(pe.Event(pe.PREFILL_CHUNK, start, unit,
                            args=dict(chunk, tick=n)))
    if decode:
        evs.append(pe.Event(pe.STEP_DECODE, start + 2 * unit,
                            wait_end - start - 2 * unit,
                            args=dict(decode, tick=n)))
        evs.append(pe.Event("serve/decode_dispatch", start + 2 * unit,
                            unit / 10, args={"tick": n}))
    if wait_end is not None:
        evs.append(pe.Event(pe.DECODE_WAIT, wait_end - unit / 2, unit / 2,
                            args={"tick": n}))
    return evs


def test_tick_intervals_run_from_wait_to_wait():
    evs = (_tick(1, 1.0, 1.5, decode={"ctx_tokens": 5})
           + _tick(2, 1.6, None, chunk={"tokens": 8, "start": 0})  # no wait
           + _tick(3, 1.8, 2.4, chunk={"tokens": 8, "start": 8},
                   decode={"ctx_tokens": 6})
           + _tick(4, 9.0, 9.5, decode={"ctx_tokens": 7}))
    got = costs_latent.ticks_with_device_work(evs, (0.9, 5.0))
    # tick 1 starts at its own first span; tick 3 reaches back to tick 1's
    # wait, over the tick that waited for nothing; tick 4 ends past the window
    assert [interval for interval, _ in got] == [
        (pytest.approx(1.02), pytest.approx(1.5)),
        (pytest.approx(1.5), pytest.approx(2.4))]
    assert [len(work) for _, work in got] == [1, 2]


class _Obs:
    """What the readers touch of a run's observations."""
    device_kind = "TPU v5 lite"
    window = (0.0, 100.0)

    def __init__(self, config):
        self.cell = type("Cell", (), {"config": config})()
        self.trace = type("Trace", (), {"window": (0.0, 100.0)})()

    @staticmethod
    def outside_stall(t):
        return True


def _op(name, start, dur, scope, program="jit_decode_step_g", kernel=True):
    return xn.NamedOp(name, start, dur, 0, program,
                      'custom_call_target="tpu_custom_call"' if kernel else "",
                      "custom-call" if kernel else "fusion", False, scope)


def _patched(monkeypatch, reader, ops, evs):
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: None if ops is None else (ops, [0], 0.0))
    if hasattr(reader, "pe"):
        monkeypatch.setattr(reader.pe, "events", lambda: evs)


def test_paged_roofline_reader_on_hand_built_ticks(monkeypatch):
    """Two decode ticks of 10,000 and 30,000 cached tokens over 5 layers,
    each ended by its wait; the kernel's calls that ended between the waits
    took 1 ms together; a call in a prefill program, one after the last wait
    and another kernel's are left out. (A step in flight:
    ``test_benchmark_decode_rooflines.py``.)"""
    reader = cells.load_module(REPO, BENCH, "metrics", "latent_paged_roofline")
    ticks = (_tick(1, 0.98, 2.0, decode={"ctx_tokens": 10_000})
             + _tick(2, 2.98, 4.0, decode={"ctx_tokens": 30_000})
             + _tick(3, 4.98, 6.0, decode={"batch": 0}))
    scope = "jit(decode_step_g)/attn/latent_paged"
    ops = [_op("latent_paged_attention.3", 1.1, 0.0004, scope),
           _op("latent_paged_attention.3", 3.1, 0.0006, scope),
           _op("latent_paged_attention.3", 3.5, 0.5, scope,
               program="jit_prefill_chunk_g"),
           _op("latent_paged_attention.3", 7.0, 0.5, scope),
           _op("paged_attention.1", 3.6, 0.5, scope)]
    _patched(monkeypatch, reader, ops, ticks)
    least = 5 * 40_000 * 1152 / 819e9
    assert reader.read(_Obs(HF)) == pytest.approx(100 * least / 0.001)
    _patched(monkeypatch, reader, ops[-1:], ticks)     # no such kernel
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, None, ticks)         # no trace to read
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, ops, ticks)          # another family
    assert reader.read(_Obs({"num_hidden_layers": 3})) is None


def test_prefill_roofline_reader_on_hand_built_ticks(monkeypatch):
    """A tick with a 2,048-token chunk at start 4,096 and a decode batch:
    the prefill kernel's five calls took 10 ms inside it."""
    reader = cells.load_module(REPO, BENCH, "metrics",
                               "latent_prefill_roofline")
    evs = _tick(7, 1.0, 1.6, chunk={"tokens": 2048, "start": 4096},
                decode={"ctx_tokens": 100})
    scope = "jit(prefill_chunk_g)/attn/latent_prefill/x"
    ops = [_op("latent_prefill_attention.2", 1.05 + 0.01 * i, 0.002, scope,
               program="jit_prefill_chunk_g") for i in range(5)]
    ops.append(_op("latent_prefill_attention.2", 3.0, 0.5, scope))  # no tick
    ops.append(_op("fusion.3", 1.2, 0.5, scope, kernel=False))  # the gather
    _patched(monkeypatch, reader, ops, evs)
    pairs = 2048 * 4096 + 2048 * 2049 // 2
    least = 5 * pairs * 2 * (192 + 128) * 32 / 197e12
    assert reader.read(_Obs(HF)) == pytest.approx(100 * least / 0.010)
    _patched(monkeypatch, reader, ops[-1:], evs)
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, ops, [])             # the parent's spans
    assert reader.read(_Obs(HF)) is None


def test_grouped_roofline_reader_on_hand_built_ticks(monkeypatch):
    """A chunk whose 65,536 rows touched all 1,024 experts of four layers
    (memory-bound) and a decode batch of 1,024 rows on 640 (memory-bound
    too), 40 ms under the scope or in a grouped-matmul call."""
    reader = cells.load_module(REPO, BENCH, "metrics", "moe_grouped_roofline")
    evs = _tick(7, 1.0, 1.6,
                chunk={"tokens": 2048, "start": 0, "expert_rows": 65_536,
                       "experts_touched": 1024},
                decode={"ctx_tokens": 9, "expert_rows": 1024,
                        "experts_touched": 640})
    ops = [_op("ragged-dot.4", 1.1, 0.030, "", kernel=False),
           _op("fusion.9", 1.2, 0.010,
               "jit(prefill_chunk_g)/moe/experts/gather", kernel=False),
           _op("fusion.8", 1.3, 0.5, "jit(prefill_chunk_g)/moe/shared/dot",
               kernel=False),
           _op("ragged-dot.4", 5.0, 0.5, "", kernel=False)]     # in no tick
    _patched(monkeypatch, reader, ops, evs)
    least = (1024 + 640) * 9_437_184 / 819e9
    assert reader.read(_Obs(HF)) == pytest.approx(100 * least / 0.040)
    _patched(monkeypatch, reader, ops, _tick(7, 1.0, 1.6,
                                             decode={"ctx_tokens": 9}))
    assert reader.read(_Obs(HF)) is None               # spans that count nothing


def test_share_reader_on_hand_built_operations(monkeypatch):
    reader = cells.load_module(REPO, BENCH, "metrics", "latent_attn_share")
    ops = [_op("fusion.1", 1.0, 0.15, "jit(decode_step_g)/attn/latent_q/dot",
               kernel=False),
           _op("latent_paged_attention.3", 1.0, 0.05,
               "jit(decode_step_g)/attn/latent_paged/x"),
           _op("fusion.2", 1.0, 0.5, "jit(decode_step_g)/attn/out/dot",
               kernel=False),
           _op("fusion.9", 1.0, 0.5, "jit(decode_step_g)/moe/experts/gather",
               kernel=False)]
    _patched(monkeypatch, reader, ops, [])
    monkeypatch.setattr(reader.tr, "busy_seconds", lambda trace, window: 2.0)
    assert reader.read(_Obs({})) == pytest.approx(10.0)
    _patched(monkeypatch, reader, ops[2:], [])         # no latent scope
    assert reader.read(_Obs({})) is None
    _patched(monkeypatch, reader, None, [])
    assert reader.read(_Obs({})) is None


def test_grouped_share_counts_the_scope_and_the_grouped_calls(monkeypatch):
    """The operations the roofline divides by, over busy time: the scope's
    own and XLA's ``ragged-dot-<...>`` calls, which carry their own name
    where the scope would be."""
    reader = cells.load_module(REPO, BENCH, "metrics", "moe_grouped_share")
    ops = [_op("ragged-dot-none.4", 1.0, 0.30, "ragged-dot-none:",
               program="jit_prefill_chunk_g", kernel=False),
           _op("fusion.9", 1.4, 0.10,
               "jit(prefill_chunk_g)/moe/experts/gather", kernel=False),
           _op("fusion.8", 1.6, 0.5, "jit(prefill_chunk_g)/moe/shared/dot",
               kernel=False),
           _op("fusion.7", 2.2, 0.5, "jit(prefill_chunk_g)/moe/router/dot",
               kernel=False)]
    _patched(monkeypatch, reader, ops, [])
    monkeypatch.setattr(reader.tr, "busy_seconds", lambda trace, window: 2.0)
    assert reader.read(_Obs({})) == pytest.approx(20.0)
    _patched(monkeypatch, reader, ops[2:], [])         # no expert operation
    assert reader.read(_Obs({})) is None
    _patched(monkeypatch, reader, None, [])            # no named trace
    assert reader.read(_Obs({})) is None


def test_rows_per_touched_expert_on_hand_built_spans(monkeypatch):
    reader = cells.load_module(REPO, BENCH, "metrics",
                               "moe_rows_per_touched_expert")
    evs = [pe.Event(pe.PREFILL_CHUNK, 1.0, 0.1,
                    args={"expert_rows": 65_536, "experts_touched": 1024}),
           pe.Event(pe.STEP_DECODE, 2.0, 0.1,
                    args={"expert_rows": 1024, "experts_touched": 640}),
           pe.Event(pe.STEP_DECODE, 3.0, 0.1, args={"batch": 3})]
    monkeypatch.setattr(reader.pe, "events", lambda: evs)
    assert reader.read(_Obs(HF)) == pytest.approx(66_560 / 1664)
    monkeypatch.setattr(reader.pe, "events", lambda: evs[2:])
    assert reader.read(_Obs(HF)) is None


@pytest.mark.parametrize("metric", ["latent_paged_roofline",
                                    "latent_prefill_roofline",
                                    "moe_grouped_roofline"])
def test_roofline_readers_are_sweeps(monkeypatch, metric):
    """200,000 operations against 20,000 ticks in well under the README's
    seconds: one bisection an operation, never ticks x operations."""
    reader = cells.load_module(REPO, BENCH, "metrics", metric)
    evs = []
    for i in range(20_000):
        evs += _tick(i, 0.004 * i, 0.004 * i + 0.0035,
                     chunk={"tokens": 64, "start": 0, "expert_rows": 2048,
                            "experts_touched": 900},
                     decode={"ctx_tokens": 1000, "expert_rows": 64,
                             "experts_touched": 60}, unit=0.0001)
    name, scope, program = {
        "latent_paged_roofline": ("latent_paged_attention.1",
                                  "x/attn/latent_paged", "jit_decode_step_g"),
        "latent_prefill_roofline": ("latent_prefill_attention.1",
                                    "x/attn/latent_prefill/y",
                                    "jit_prefill_chunk_g"),
        "moe_grouped_roofline": ("ragged-dot.1", "", "jit_decode_step_g"),
    }[metric]
    ops = [_op(name, 0.004 * (i // 10) + 0.0005 + 0.0002 * (i % 10),
               0.00005, scope, program=program) for i in range(200_000)]
    _patched(monkeypatch, reader, ops, evs)
    t0 = time.monotonic()
    assert reader.read(_Obs(HF)) > 0
    assert time.monotonic() - t0 < 5.0
