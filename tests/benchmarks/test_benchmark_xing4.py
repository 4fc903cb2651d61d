"""The files ISSUE 37 adds to the benchmark, rehearsed on the CPU: the
``xing4_0`` family and reference, a toy configuration (four residual streams
round every sublayer, YaRN on the latent attention's rotary part) and a toy
decode-heavy traffic mix, the two new metric readers and the cost functions
beside them, through the same runner and result line as ``benchmarks/run.py``
and with no edit to a file that was there (``benchmark_rehearsal.py``)."""

import json
import shutil

import pytest

import benchmark_rehearsal as rehearsal
import test_benchmark_contract as contract
from benchmarks.harness import cells, costs_hc
from benchmarks.harness import program_events as pe
from benchmarks.harness import xplane_names as xn

REPO = rehearsal.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL, CONFIG, MIX = "tiny-reasoning", "tiny-xing4", "tiny-reasoning"
LIKE = "xing4-serve-reasoning"
REAL_NAME = "xing4.0-29b-a4b-serve-d7"
REAL = REPO / "benchmarks" / "configs" / f"{REAL_NAME}.json"
NEW_METRICS = ("hc_mix_share", "hc_mix_roofline")
HF = json.loads(REAL.read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal's checkout plus the toy cell, as files and entries."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench_xing4"))
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
    # both leading dense layers and at least four expert layers after them
    assert data["num_hidden_layers"] - data["first_k_dense_replace"] >= 4
    assert data["reduced"] == ["num_hidden_layers"]


def test_configuration_is_the_catalog_row_but_for_depth(bench):
    """Every key of the published config at its published value, except the
    depth (the row is copied here: the catalog lies outside the
    repository)."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    differ = {k for k, v in published.items() if HF.get(k, "absent") != v}
    assert differ == set(HF["reduced"]) == {"num_hidden_layers"}
    assert HF["published"] == {"num_hidden_layers": 40}
    assert HF["num_hidden_layers"] == 7
    assert len(HF["assumed"]) >= 8
    assert "9,841,733,492" in HF["deployment"]
    entry, = [c for c in bench["configs"] if c["name"] == REAL_NAME]
    assert entry["source"] == HF["source"]
    cell, = [w for w in bench["workloads"] if w["name"] == LIKE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (REAL_NAME, "reasoning", 1)


def test_traffic_is_the_issues_letter_for_letter():
    from benchmarks.harness import traffic
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "reasoning.json").read_text())
    assert {k: mix[k] for k in ("loop", "clients", "max_concurrency",
                                "block_requests", "ramp_s", "drain_s")} == {
        "loop": "closed", "clients": 64, "max_concurrency": 64,
        "block_requests": 64, "ramp_s": 24.0, "drain_s": 20.0}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 0.7, "min": 128, "max": 2048}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 512,
                                    "max": 2048}
    assert "order_seed" not in mix and isinstance(mix["pairing_seed"], int)
    prompts = traffic.quantile_lengths(mix["prompt_tokens"], 64)
    outputs = traffic.quantile_lengths(mix["output_tokens"], 64)
    assert (prompts[0], prompts[-1]) == (141, 2048)
    assert 900 < prompts.mean() < 1000 and round(outputs.mean()) == 1280
    # decode-heavy: more tokens come out than go in
    assert outputs.sum() > prompts.sum()


def test_new_metrics_list_the_cell_and_move_tokens_per_second(bench):
    """Membership only: a later cell may join any of these lists, and this
    one any list it reports, with no edit here."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert LIKE in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
        assert by_name[name]["source"] == "device_trace"
    assert by_name["hc_mix_share"]["layer"] == "model step"
    assert by_name["hc_mix_share"]["better"] == "lower"
    assert by_name["hc_mix_roofline"]["layer"] == "kernels"
    tokens, = [m for m in bench["end_to_end"]
               if m["name"] == "serve_tokens_per_s"]
    assert LIKE in tokens["workloads"]


def test_family_builds_the_published_widths_and_the_cells_shapes():
    family = cells.load_module(REPO, BENCH, "families", "xing4_0")
    cfg, _ = family.build(HF, "serve", HF["serve"])
    assert (cfg.num_layers, cfg.first_k_dense_replace, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.vocab_size, cfg.hidden_size,
            cfg.hc_mult, cfg.hc_sinkhorn_iters) == \
        (7, 2, 64, 4, 131072, 3584, 4, 20)
    assert cfg.rope_yarn.factor == 64.0 and cfg.yarn_mscale_all_dim == 1.0
    assert cfg.max_seq_len == HF["serve"]["max_context"] == 4160
    mix = json.loads((REPO / "benchmarks" / "traffic" /
                      "reasoning.json").read_text())
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    engine = HF["serve"]["engine"]
    assert longest <= cfg.max_seq_len
    assert engine["ctx_block_buckets"][-1] * 64 >= longest
    # 64 streams live: a decode bucket and a scheduler that hold them all
    assert engine["decode_batch_buckets"][-1] == 64 == mix["max_concurrency"]
    assert engine["scheduler"]["max_decode_batch"] == 64
    assert engine["kv_num_blocks"] * 0.95 > 64 * -(-longest // 64)
    # the check's wave: more sequences than the smaller batch bucket holds,
    # one past the middle context bucket, one prefilled in several chunks
    check = HF["serve"]["check"]
    assert len(check["others"]) + 1 > engine["decode_batch_buckets"][0]
    assert max(check["others"]) + check["new_tokens"] > \
        engine["ctx_block_buckets"][-2] * 64
    assert max(check["others"]) > 3 * engine["scheduler"]["max_tokens_per_step"]
    with pytest.raises(ValueError):
        family.build(HF, "train", {})


@pytest.mark.parametrize("traced", [False, True])
def test_toy_cell_is_correct_and_compiles_nothing_in_the_window(runs, traced):
    obs, line, lines = runs[traced]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert obs.counters["compiles_in_window"] == 0
    assert any("reference check" in text and "agree" in text for text in lines)
    if not traced:
        assert {"serve_tokens_per_s", "setup_s"} <= set(line["metrics"])


def test_traced_run_leaves_the_device_trace_metrics_out_on_the_cpu(runs):
    """Device-trace metrics need a TPU plane: their readers find nothing on
    the CPU, return None and are left out, as on a program without them."""
    obs, line, _ = runs[True]
    assert not any(name in line["metrics"] for name in NEW_METRICS)
    for name in NEW_METRICS:
        reader = cells.load_module(REPO, BENCH, "metrics", name)
        value = reader.read(obs)
        assert value is None or value > 0, name


def test_traced_toy_run_decodes_in_its_widest_bucket(runs):
    """The spans the cost functions count from carry what they read."""
    obs, _, _ = runs[True]
    evs = pe.inside(pe.events(), obs.window)   # the ring is the process's
    decodes = [e for e in evs if e.name == pe.STEP_DECODE]
    chunks = [e for e in evs if e.name == pe.PREFILL_CHUNK]
    assert decodes and chunks
    assert max(e.arg("bucket") for e in decodes) == 8
    assert all(e.arg("batch") is not None for e in decodes)
    assert all(e.arg("tokens") is not None for e in chunks)


# --- the cost functions and the readers, by hand -------------------------------

def test_mixing_costs_by_hand():
    # before: 4 x 3584 read, 3584 written; after: 4 x 3584 + 3584 read,
    # 4 x 3584 written; bfloat16
    by_hand = 2 * ((4 * 3584 + 3584) + (4 * 3584 + 3584 + 4 * 3584))
    assert by_hand == 100_352
    assert costs_hc.mix_bytes_per_token_sublayer(HF, 2) == by_hand
    # a 2,048-token chunk: 0.2055 GB a sublayer, 14 sublayers
    assert costs_hc.mix_bytes(2048, HF, 2) == 2048 * 14 * by_hand
    assert costs_hc.mix_bytes(2048, HF, 2) / 14 == pytest.approx(0.2055e9,
                                                                 rel=1e-3)
    assert costs_hc.mix_bytes(64, dict(HF, num_hidden_layers=40), 2) == \
        64 * 80 * by_hand


def test_step_tokens_counts_the_rows_that_are_no_padding():
    evs = [pe.Event(pe.PREFILL_CHUNK, 1.0, 0.1,
                    args={"tokens": 700, "bucket": 1024}),
           pe.Event(pe.STEP_DECODE, 1.2, 0.5, args={"batch": 61,
                                                    "bucket": 64}),
           pe.Event(pe.PREFILL_CHUNK, 2.0, 0.1, args={"tokens": 300}),
           pe.Event(pe.STEP_DECODE, 9.0, 0.5, args={"batch": 64}),
           pe.Event("serve/plan", 1.0, 0.1, args={"tokens": 5})]
    assert costs_hc.step_tokens(evs, [(0.9, 1.8), (1.9, 2.5)]) == 1061
    assert costs_hc.step_tokens(evs, []) == 0


class _Obs:
    device_kind = "TPU v5 lite"
    window = (0.0, 100.0)

    def __init__(self, config):
        self.cell = type("Cell", (), {"config": config})()
        self.trace = type("Trace", (), {"window": (0.0, 100.0)})()


def _op(name, start, dur, scope, program="jit_decode_step_g"):
    return xn.NamedOp(name, start, dur, 0, program, "", "fusion", False,
                      scope)


def _patched(monkeypatch, reader, ops, evs):
    monkeypatch.setattr(reader.named_readers, "named_ops",
                        lambda obs: None if ops is None else (ops, [0], 0.0))
    if hasattr(reader, "pe"):
        monkeypatch.setattr(reader.pe, "events", lambda: evs)


def _tick(n, start, spans):
    """A tick's spans, each (name, offset, seconds, args), and the wait that
    ends it."""
    evs = [pe.Event(name, start + at, dur, args=dict(args, tick=n))
           for name, at, dur, args in spans]
    end = max(e.end for e in evs)
    return evs + [pe.Event(pe.DECODE_WAIT, end, 0.01, args={"tick": n})]


def test_roofline_reader_on_hand_built_ticks(monkeypatch):
    """Two ticks that end in a wait: a chunk of 700 tokens with a decode
    batch of 61, then a batch of 64; the mixing's operations inside them took
    2 ms together; one outside any tick and the experts' are left out."""
    reader = cells.load_module(REPO, BENCH, "metrics", "hc_mix_roofline")
    evs = [pe.Event(pe.DECODE_WAIT, 0.5, 0.01, args={"tick": 0})] \
        + _tick(1, 1.0, [(pe.PREFILL_CHUNK, 0.0, 0.1, {"tokens": 700}),
                         (pe.STEP_DECODE, 0.1, 0.3, {"batch": 61})]) \
        + _tick(2, 2.0, [(pe.STEP_DECODE, 0.0, 0.3, {"batch": 64})])
    pre = "jit(decode_step_g)/hc/pre/exp"
    post = "jit(prefill_chunk_g)/hc/post/add"
    ops = [_op("fusion.1", 1.05, 0.0010, post, "jit_prefill_chunk_g"),
           _op("fusion.2", 1.20, 0.0006, pre),
           _op("fusion.3", 2.10, 0.0004, "jit(decode_step_g)/hc/head/sum"),
           _op("fusion.4", 50.0, 0.5, pre),
           _op("grouped_matmul.1", 2.2, 0.5, "x/moe/experts")]
    _patched(monkeypatch, reader, ops, evs)
    need = (700 + 61 + 64) * 14 * 100_352
    assert reader.read(_Obs(HF)) == pytest.approx(
        100 * need / 819e9 / 0.002)
    _patched(monkeypatch, reader, ops[-1:], evs)       # no such scope
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, None, evs)           # no trace to read
    assert reader.read(_Obs(HF)) is None
    _patched(monkeypatch, reader, ops, evs)            # another family
    assert reader.read(_Obs({"num_hidden_layers": 3})) is None
    _patched(monkeypatch, reader, ops, [])             # the parent's ring
    assert reader.read(_Obs(HF)) is None


def test_share_reader_on_hand_built_operations(monkeypatch):
    reader = cells.load_module(REPO, BENCH, "metrics", "hc_mix_share")
    ops = [_op("fusion.1", 1.0, 0.15, "jit(decode_step_g)/hc/pre/exp"),
           _op("fusion.2", 1.0, 0.04, "jit(prefill_chunk_g)/hc/post/add"),
           _op("fusion.3", 1.0, 0.01, "jit(decode_step_g)/hc/head/sum"),
           _op("fusion.4", 1.0, 0.5, "jit(decode_step_g)/attn/out/dot"),
           _op("fusion.5", 1.0, 0.5, "jit(decode_step_g)/mhc/other")]
    _patched(monkeypatch, reader, ops, [])
    monkeypatch.setattr(reader.tr, "busy_seconds", lambda trace, window: 2.0)
    assert reader.read(_Obs({})) == pytest.approx(10.0)
    _patched(monkeypatch, reader, ops[3:], [])         # no such scope
    assert reader.read(_Obs({})) is None
    _patched(monkeypatch, reader, None, [])
    assert reader.read(_Obs({})) is None
