"""The harness end to end at a tiny size on the CPU: the same runners, metric
readers and result line as ``benchmarks/run.py``, on cells that are dropped
into a temporary copy of the benchmark as files and entries alone."""

import json
import os
import subprocess
import sys

import pytest

import benchmark_rehearsal as rehearsal
import test_benchmark_contract as contract
from benchmarks.harness import cells, run_serve

REPO = rehearsal.REPO
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def runs(root):
    """Every tiny cell once untraced and once traced."""
    out = {}
    for name, *_ in rehearsal.TINY_CELLS:
        for traced in (False, True):
            lines = []
            obs, line = rehearsal.run_cell(root, name, 2.0, traced,
                                           lines=lines)
            out[name, traced] = (obs, line, lines)
    return out


CASES = [(name, traced) for name, *_ in rehearsal.TINY_CELLS
         for traced in (False, True)]


@pytest.mark.parametrize("name,traced", CASES)
def test_last_line_has_exactly_the_contract_keys(runs, name, traced):
    _, line, _ = runs[name, traced]
    assert set(line) - {"breakdown"} == CONTRACT_KEYS
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    if traced:
        assert {"busy_s", "window_s"} <= set(line["device"])
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)


@pytest.mark.parametrize("name,traced", CASES)
def test_tiny_cell_is_correct_and_compiles_nothing_in_the_window(runs, name,
                                                                 traced):
    obs, line, _ = runs[name, traced]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert obs.counters["compiles_in_window"] == 0


@pytest.mark.parametrize("name,expected", [
    ("tiny-train", {"train_tokens_per_s_per_chip", "setup_s"}),
    ("tiny-train-x4", {"train_tokens_per_s_per_chip", "setup_s"}),
    ("tiny-chat", {"serve_tpot_p50_ms", "setup_s"}),
    ("tiny-rag", {"serve_tokens_per_s", "setup_s"}),
    ("tiny-moe-shared", {"serve_tokens_per_s", "setup_s"}),
])
def test_untraced_run_reports_the_cells_end_to_end_metrics(runs, name, expected):
    _, line, _ = runs[name, False]
    assert expected <= set(line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("name,expected", [
    ("tiny-chat", {"serve_ttft_upper_quartile_ms", "serve_ttft_p90_ms",
                   "serve_tpot_request_p90_ms", "serve_tpot_upper_quartile_ms",
                   "serve_tpot_mean_ms", "loadgen_late_p99_ms",
                   "serve_queue_wait_p50_ms", "engine_step_p50_ms",
                   "decode_batch_mean"}),
    ("tiny-rag", {"prefill_tokens_per_tick"}),
    ("tiny-moe-shared", {"prefill_tokens_per_tick",
                         "prefill_chunks_per_tick"}),
])
def test_traced_run_reports_host_side_per_layer_metrics(runs, name, expected):
    """Device-trace metrics need a TPU plane; their readers find nothing on
    the CPU and the harness leaves them out of the line."""
    _, line, _ = runs[name, True]
    assert expected <= set(line["metrics"])
    assert not any(k.endswith("_roofline") or k.endswith("_share")
                   for k in line["metrics"])


def test_chat_serves_the_scheduled_multiset(runs):
    obs, line, _ = runs["tiny-chat", False]
    measured = [r for r in obs.requests if r.measured]
    assert line["attempted"] == len(measured) == round(
        obs.cell.traffic["rate_rps"] * 2.0)
    assert all(r.ok and len(r.stamps) == r.asked for r in measured)
    assert all(r.stamps == sorted(r.stamps) and r.stamps[0] >= r.sent
               for r in measured)
    assert obs.counters["ladder_transitions"] == 0
    assert obs.counters["requests_shed"] == obs.counters["requests_rejected"] == 0


def test_traced_serving_run_excludes_the_profilers_stall(runs):
    obs, _, _ = runs["tiny-chat", True]
    (a, b), = obs.host_exclude
    assert obs.window[0] < a < b
    assert not obs.outside_stall((a + b) / 2) and obs.outside_stall(a - 1)
    assert obs.trace.window[0] >= a and obs.trace.window[1] <= b
    assert obs.ticks and all(t[1] >= t[0] for t in obs.ticks)


def test_training_cell_checks_loss_against_the_reference(runs):
    obs, _, lines = runs["tiny-train", False]
    t = obs.train
    assert abs(t["first_loss"] - t["reference_loss"]) < 0.02 * t["reference_loss"]
    assert t["last_loss"] < t["first_loss"]
    assert t["tokens_per_step"] == 4 * 128
    assert any("float32 reference" in text for text in lines)


def test_four_chip_cell_shards_the_state_and_matches_the_reference(runs):
    """ZeRO-3 over four (virtual) devices: one row a chip, the same tokens a
    step, the first loss against the reference on sharded weights."""
    obs, line, _ = runs["tiny-train-x4", False]
    assert line["device"]["count"] == 4 and obs.train["chips"] == 4
    assert obs.train["tokens_per_step"] == 4 * 128
    solo = runs["tiny-train", False][0].train
    assert obs.train["first_loss"] == pytest.approx(solo["first_loss"], rel=2e-2)
    assert obs.train["reference_loss"] == pytest.approx(solo["reference_loss"],
                                                        rel=1e-5)


def test_warm_up_enumerates_the_reachable_programs(bench):
    """The sets come from the engine's own ladders and the traffic's bounds."""
    from deepspeed_tpu.inference.v2.engine_v2 import V2EngineConfig
    chat = cells.find_cell(bench, "mistral7b-serve-chat", REPO).traffic
    prefill, decode = run_serve.reachable_shapes(V2EngineConfig(), chat)
    # prompts to 2048 tokens reach context buckets 4..32; the largest chunk
    # bucket only ends past 1024 tokens
    assert (128, 4) in prefill and (128, 32) in prefill
    assert (2048, 32) in prefill and (2048, 16) not in prefill
    assert all(m <= 32 for _, m in prefill)
    # contexts to 2560 tokens reach the 64-block table; batches to the
    # traffic file's max_concurrency (32)
    assert {(1, 4), (32, 64), (16, 16)} <= set(decode) and len(decode) == 30
    assert max(d for d, _ in decode) == 32
    rag = cells.find_cell(bench, "mixtral8x7b-serve-batch-rag", REPO).traffic
    prefill, decode = run_serve.reachable_shapes(V2EngineConfig(), rag)
    assert {m for _, m in decode} == {32, 64}
    assert max(d for d, _ in decode) == 32          # 32 callers
    assert (128, 64) in prefill


SERVED_CONFIGS = ["mistral-7b-serve-d8", "mixtral-8x7b-serve-d3"]


@pytest.mark.parametrize("config", SERVED_CONFIGS)
def test_engine_group_of_a_served_cell_builds_what_the_flat_keys_built(config):
    """``{"kv_num_blocks": n}`` gives the engine configuration that
    ``V2EngineConfig(**group)`` gave before the group reached nested fields."""
    from deepspeed_tpu.inference.v2.engine_v2 import V2EngineConfig
    data = json.loads((REPO / "benchmarks" / "configs" /
                       f"{config}.json").read_text())
    group = data["serve"]["engine"]
    assert set(group) == {"kv_num_blocks"}
    assert run_serve.engine_config(group) == V2EngineConfig(**group)


def test_engine_group_reaches_nested_groups_and_ladders():
    from deepspeed_tpu.inference.v2.engine_v2 import V2EngineConfig
    from deepspeed_tpu.inference.v2.sampling import SamplingConfig
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    ecfg = run_serve.engine_config({
        "kv_num_blocks": 2048, "ctx_block_buckets": [4, 8, 16, 32, 64, 80],
        "scheduler": {"max_tokens_per_step": 4096,
                      "prefill_buckets": [256, 1024, 4096]},
        "sampling": {"temperature": 0.7, "top_k": 40}})
    assert ecfg == V2EngineConfig(
        kv_num_blocks=2048, ctx_block_buckets=(4, 8, 16, 32, 64, 80),
        scheduler=SchedulerConfig(max_tokens_per_step=4096,
                                  prefill_buckets=(256, 1024, 4096)),
        sampling=SamplingConfig(temperature=0.7, top_k=40))
    # the cell ISSUE 29 sized: contexts to 5,120 tokens = 80 blocks
    mix = {"prompt_tokens": {"min": 1024, "max": 4096},
           "output_tokens": {"min": 256, "max": 1024}, "max_concurrency": 64}
    prefill, decode = run_serve.reachable_shapes(ecfg, mix)
    assert {b for b, _ in prefill} == {256, 1024, 4096}
    assert max(m for _, m in prefill) == 64           # 4,096 tokens
    assert {m for _, m in decode} == {32, 64, 80}     # 1,025 .. 5,120 tokens
    assert max(d for d, _ in decode) == 64


@pytest.mark.parametrize("group,names", [
    ({"kv_blocks": 8}, "kv_blocks"),
    ({"scheduler": {"chunk_budget": 8}}, "chunk_budget"),
    ({"scheduler": 2048}, "serve.engine.scheduler"),
    ({"ctx_block_buckets": 64}, "serve.engine.ctx_block_buckets"),
])
def test_engine_group_refuses_what_the_engine_lacks(group, names):
    with pytest.raises(cells.CellError, match=names):
        run_serve.engine_config(group)


def test_third_family_arrives_as_files_and_entries_alone(root):
    """``model_type: qwen2_moe``: a configuration that cuts depth, experts
    and vocabulary, its family and reference files, a cell and a per-layer
    metric, with no edit to a file that was there; the contract's rule reads
    the cut from the file."""
    assert rehearsal.files_that_differ(root) == []
    bench = cells.load_benchmark(root)
    entry, = [c for c in bench["configs"] if c["name"] == "tiny-qwen2-moe"]
    data = json.loads((root / entry["file"]).read_text())
    assert data["model_type"] == "qwen2_moe"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert contract.configuration_faults(entry, data) == []
    assert (data["num_experts"], data["num_hidden_layers"],
            data["vocab_size"] * 8) == (8, 4, data["published"]["vocab_size"])
    for folder in ("families", "reference"):
        assert not (REPO / "benchmarks" / folder / "qwen2_moe.py").exists()
        assert cells.load_module(root, bench, folder, "qwen2_moe") is not None
    repo_bench = cells.load_benchmark(REPO)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        was = repo_bench[key]
        now = [dict(e, workloads=[w for w in e["workloads"]
                                  if not w.startswith("tiny-")])
               if "workloads" in e else e for e in bench[key][:len(was)]]
        assert now == was                   # entries appended, none edited


def test_third_family_joins_the_file_that_ships(root, bench):
    """"The harness takes a new ``model_type`` without an edit", against the
    file that ships and not only the tiny root's: the toy entries appended
    to a copy of the real ``BENCHMARK.json`` (as it is, and with a later
    family ahead of them). The contract's rule passes the third family's
    configuration, the loader finds every toy cell's files with the metrics
    its kind of cell reports, and every cell that was there still finds its
    own and reports what it reported."""
    joined = rehearsal.with_tiny_cells(bench)
    entry, = [c for c in joined["configs"] if c["name"] == "tiny-qwen2-moe"]
    data = json.loads((root / entry["file"]).read_text())
    assert contract.configuration_faults(entry, data) == []
    for folder in ("families", "reference"):
        assert cells.load_module(root, joined, folder,
                                 data["model_type"]) is not None
    toys = {name for name, *_ in rehearsal.TINY_CELLS}
    for entry in joined["workloads"]:
        name = entry["name"]
        if name in toys:
            found = cells.find_cell(joined, name, root)
            reported = {m["name"] for m in found.end_to_end}
            assert "setup_s" in reported and len(reported) >= 2, name
            assert found.per_layer, name
            assert all(m["moves"] in reported for m in found.per_layer), name
        else:
            was = cells.find_cell(bench, name, REPO)
            now = cells.find_cell(joined, name, REPO)
            assert (now.config, now.traffic) == (was.config, was.traffic)
            for kind in ("end_to_end", "per_layer"):
                assert [m["name"] for m in getattr(now, kind)] == \
                    [m["name"] for m in getattr(was, kind)], name
    shared = cells.find_cell(joined, "tiny-moe-shared", root)
    assert shared.config["model_type"] == "qwen2_moe"
    assert "prefill_chunks_per_tick" in {m["name"] for m in shared.per_layer}


def test_warm_up_follows_the_ladders_the_third_familys_file_gave(root, runs):
    """``serve.engine`` sets the scheduler's budget and both ladders; the
    engine holds them as its dataclasses and tuples, and the warm-up
    enumerates them and nothing of the defaults'."""
    bench = cells.load_benchmark(root)
    cell = cells.find_cell(bench, "tiny-moe-shared", root)
    group = cell.config["serve"]["engine"]
    ecfg = run_serve.engine_config(group)
    assert ecfg.scheduler.max_tokens_per_step == 160
    assert ecfg.scheduler.prefill_buckets == (32, 96, 160)
    assert ecfg.ctx_block_buckets == (4, 8, 24)
    prefill, decode = run_serve.reachable_shapes(ecfg, cell.traffic)
    assert {b for b, _ in prefill} == set(group["scheduler"]["prefill_buckets"])
    assert {m for _, m in prefill + decode} <= set(group["ctx_block_buckets"])
    assert (160, 24) in prefill and (4, 24) in decode
    _, _, lines = runs["tiny-moe-shared", False]
    assert any(f"warmed {len(prefill)} prefill and {len(decode)} decode"
               in text for text in lines)


@pytest.mark.parametrize("shared_expert,agrees", [(True, True), (False, False)])
def test_reference_check_fails_without_the_shared_expert(root, shared_expert,
                                                         agrees):
    """The third family's reference check has the power it is there for: a
    reference that leaves the shared expert out differs from the engine by
    more than the tolerance."""
    import types
    bench = cells.load_benchmark(root)
    cell = cells.find_cell(bench, "tiny-moe-shared", root)
    server, family, _ = run_serve.build_server(cell, bench, 3)
    plain = cells.load_module(root, bench, "reference", "qwen2_moe")

    def weights(params):
        view = family.reference_weights(params)
        if not shared_expert:
            for layer in view["layers"]:
                layer["shared"] = dict(layer["shared"],
                                       down=0.0 * layer["shared"]["down"])
        return view
    said = []
    ok = run_serve.check_against_reference(
        server.engine, types.SimpleNamespace(reference_weights=weights),
        plain, cell.config, 3, said.append)
    assert ok is agrees, said[-1]


@pytest.mark.parametrize("noise,agrees", [(0.0, True), (1.0, False)])
def test_reference_check_holds_every_wave_sequence_to_the_tolerance(
        root, noise, agrees):
    """The check passes against the plain reference and fails against one
    whose logits are off by more than the tolerance: it has the power it is
    there for, over every sequence of the wave and not the first alone."""
    import types

    import jax
    bench = cells.load_benchmark(root)
    cell = cells.find_cell(bench, "tiny-chat", root)
    server, family, _ = run_serve.build_server(cell, bench, 3)
    plain = cells.load_module(root, bench, "reference",
                              cell.config["model_type"])

    def logits(weights, hf, seq):
        out = plain.logits(weights, hf, seq)
        key = jax.random.PRNGKey(len(seq))
        return out + noise * jax.random.normal(key, out.shape)
    said = []
    ok = run_serve.check_against_reference(
        server.engine, family, types.SimpleNamespace(logits=logits),
        cell.config, 3, said.append)
    assert ok is agrees
    check = cell.config["serve"]["check"]
    positions = (2 + len(check["others"])) * (1 + check["new_tokens"])
    assert f"{positions} positions of {2 + len(check['others'])} sequences" in said[-1]
    assert ("agree" if agrees else "DIFFER") in said[-1]


def test_a_dropped_in_metric_file_is_found_and_read(root, runs):
    """A later PR adds a per-layer metric as one file and one entry."""
    from benchmarks.harness import result
    (root / "benchmarks" / "metrics" / "tokens_streamed.new.py").write_text(
        "def read(obs):\n"
        "    return float(sum(len(r.stamps) for r in obs.requests))\n")
    bench = cells.load_benchmark(root)
    bench["per_layer"].append({
        "name": "tokens_streamed.new", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "benchmark load generator",
        "moves": "serve_tpot_p50_ms", "workloads": ["tiny-chat"]})
    cell = cells.find_cell(bench, "tiny-chat", root)
    obs, _, _ = runs["tiny-chat", True]
    got = result.read_metrics(cell, bench, obs, True, lambda text: None)
    assert got["tokens_streamed.new"]["value"] > 0
    # nothing that was there was edited
    for rel in ("harness/run_serve.py", "harness/result.py", "run.py"):
        assert (root / "benchmarks" / rel).read_bytes() == \
            (REPO / "benchmarks" / rel).read_bytes()


def test_a_metric_without_a_reader_or_a_reading_is_left_out(root, runs):
    from benchmarks.harness import result
    (root / "benchmarks" / "metrics" / "reads_nothing.py").write_text(
        "def read(obs):\n    return None\n")
    bench = cells.load_benchmark(root)
    for name in ("reads_nothing", "has_no_file"):
        bench["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "engine tick",
            "moves": "serve_tpot_p50_ms", "workloads": ["tiny-chat"]})
    cell = cells.find_cell(bench, "tiny-chat", root)
    said = []
    got = result.read_metrics(cell, bench, runs["tiny-chat", True][0], True,
                              said.append)
    assert "reads_nothing" not in got and "has_no_file" not in got
    assert len([s for s in said if "left out" in s]) >= 2


def test_run_py_refuses_the_cpu_with_nothing_on_stdout():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "run.py"), "--workload",
         "mistral7b-train-8k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_run_py_fails_where_the_program_is_missing(tmp_path):
    """Alone with BENCHMARK.json and its own paths the command has no system
    to test: nonzero exit, no result line."""
    import shutil
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "mistral7b-train-8k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""
