"""dsmem tests: analytic ledger goldens, counter-track round-trips, the
watermark ratchet CLI, the chaos OOM forensics drill, and the dslint
hot-path proof for the sampler.

Deterministic by construction: ledger values are closed-form arithmetic,
the CLI exit matrix runs on checked-in fixtures (tests/mem_fixtures/ +
repo-root mem_baseline.json — regenerate BOTH with
``python tests/mem_fixtures/make_fixtures.py``), the sampler tests inject
fake device stats, and the OOM drill is seed-free chaos (``oom_step`` is
an exact step match).
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

import deepspeed_tpu
from deepspeed_tpu.models.simple import SimpleModel, random_batch
from deepspeed_tpu.telemetry.memory import (MEM_BASELINE_NAME, MemoryLedger,
                                            MemorySampler, PHASES,
                                            check_mem_baseline,
                                            estimate_zero2_model_states_mem_needs,
                                            estimate_zero3_model_states_mem_needs,
                                            is_oom_error, is_oom_message,
                                            next_offload_tier, preflight,
                                            tie_out, write_mem_baseline)
from deepspeed_tpu.telemetry.tracer import Tracer, configure_tracing, get_tracer
from serving_fakes import ResidentEngine

pytestmark = pytest.mark.mem

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = pathlib.Path(__file__).resolve().parent / "mem_fixtures"
DSTPU = str(REPO / "bin" / "dstpu")


def _engine(extra=None, seed=1):
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}}
    if extra:
        cfg.update(extra)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=32), config=cfg,
        example_batch=random_batch(4), seed=seed)
    return engine


# ---------------------------------------------------------------------------
# ledger goldens (closed-form: 1000 params, 4-way ZeRO world, bf16 compute)
# ---------------------------------------------------------------------------
def _micro(stage, **kw):
    return MemoryLedger(num_params=1000, zero_stage=stage, zero_world=4,
                        compute_dtype="bf16", **kw)


def test_ledger_golden_zero_stages():
    """Stage-by-stage HBM plan: exactly the reference sharding arithmetic
    (fp32 masters 4B/p, Adam 8B/p, fp32 grad accum 4B/p; sharded terms
    divide by the ZeRO world at their stage)."""
    # stage 0: everything replicated
    assert _micro(0).phase_bytes() == {
        "init": {"hbm_bytes": 12000, "host_bytes": 0},
        "first_step": {"hbm_bytes": 16000, "host_bytes": 0},
        "steady": {"hbm_bytes": 16000, "host_bytes": 0},
        "ckpt": {"hbm_bytes": 16000, "host_bytes": 0},
    }
    # stage 1: optimizer state / 4
    assert _micro(1).phase_bytes()["init"]["hbm_bytes"] == 6000
    assert _micro(1).phase_bytes()["steady"]["hbm_bytes"] == 10000
    # stage 2: + grads / 4
    assert _micro(2).phase_bytes()["steady"]["hbm_bytes"] == 7000
    # stage 3: + params / 4; ckpt adds the bf16 gather buffer (2B/p, full)
    s3 = _micro(3).phase_bytes()
    assert s3["init"]["hbm_bytes"] == 3000
    assert s3["steady"]["hbm_bytes"] == 4000
    assert s3["ckpt"]["hbm_bytes"] == 4000 + 2000


def test_ledger_offload_tiers():
    """Offload tiers move bytes to the host column, not into thin air."""
    opt = _micro(1, offload_optimizer="cpu").components()
    assert opt["opt_state"] == {"hbm_bytes": 0, "host_bytes": 2000}
    assert opt["grads"]["host_bytes"] == 4000   # host optimizer accumulates
    assert opt["grads"]["hbm_bytes"] == 0
    # Twin-Flow partial offload splits by ratio
    half = _micro(1, offload_optimizer="cpu",
                  offload_optimizer_ratio=0.5).components()
    assert half["opt_state"] == {"hbm_bytes": 1000, "host_bytes": 1000}
    # param offload: fp32 masters host-side, HBM holds one streamed group
    par = _micro(0, offload_param="cpu", num_layers=2,
                 layers_per_group=1).components()
    assert par["masters"] == {"hbm_bytes": 0, "host_bytes": 4000}
    assert par["params"] == {"hbm_bytes": 1000, "host_bytes": 0}


def test_ledger_activation_and_logits_terms():
    led = MemoryLedger(num_params=1000, micro_batch=2, seq_len=8,
                       hidden_size=4, num_layers=3, vocab_size=16,
                       compute_dtype="bf16",
                       remat_policy="dots_with_no_batch_dims_saveable")
    c = led.components()
    # 7 saved hidden-sized tensors per layer * 3 layers * 2B * (2*8*4)
    assert c["activations"]["hbm_bytes"] == 7 * 2 * 8 * 4 * 2 * 3
    # fp32 logits + exp temp: 2 * 4B * mb * seq * vocab
    assert c["logits"]["hbm_bytes"] == 2 * 4 * 2 * 8 * 16
    # chunked CE never materializes them
    led.loss_chunked = True
    assert led.components()["logits"]["hbm_bytes"] == 0


def test_estimate_zero_reference_apis():
    """The reference estimate_zero*_model_states_mem_needs shapes."""
    gpu, cpu = estimate_zero2_model_states_mem_needs(
        1000, num_gpus_per_node=4, cpu_offload=True)
    assert (gpu, cpu) == (2000, int(1000 * 16 * 1.5))
    gpu, cpu = estimate_zero2_model_states_mem_needs(
        1000, num_gpus_per_node=4, cpu_offload=False)
    assert gpu == 4 * 1000 + 16 * 1000 // 4
    gpu, _ = estimate_zero3_model_states_mem_needs(
        1000, largest_layer_params=100, num_gpus_per_node=4,
        cpu_offload=False)
    assert gpu == 4 * 100 + 18 * 1000 // 4
    gpu, _ = estimate_zero3_model_states_mem_needs(
        1000, largest_layer_params=100, num_gpus_per_node=4,
        cpu_offload=True, cpu_offload_params=True)
    assert gpu == 4 * 100


def test_ledger_from_config_reads_raw_keys():
    raw = {"zero_optimization": {"stage": 2,
                                 "offload_optimizer": {"device": "cpu"}},
           "bf16": {"enabled": True},
           "data_types": {"grad_accum_dtype": "bf16"},
           "optimizer": {"type": "sgd"},
           "train_micro_batch_size_per_gpu": 4,
           "activation_checkpointing": {"policy": "nothing_saveable"}}
    led = MemoryLedger.from_config(raw, num_params=1000,
                                   mesh_shape={"data": 2, "fsdp": 4})
    assert (led.zero_stage, led.zero_world) == (2, 4)
    assert led.compute_dtype == "bf16"
    assert led.optimizer_moments == 1          # sgd: one moment
    assert led.offload_optimizer == "cpu"
    assert led.grad_accum_dtype == "bf16"
    assert led.micro_batch == 4
    # grads: 2B/p sharded over 4 (stage 2), host-side (host optimizer)
    assert led.components()["grads"]["host_bytes"] == 500


def test_oom_classification():
    assert is_oom_message("RESOURCE_EXHAUSTED: out of memory allocating")
    assert is_oom_message("XlaRuntimeError: Out of memory while trying")
    assert not is_oom_message("deadline exceeded")
    assert is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: 16.0G"))


# ---------------------------------------------------------------------------
# counter events: emit -> ring -> Chrome JSON -> aggregates
# ---------------------------------------------------------------------------
def test_counter_roundtrip_chrome_and_aggregates():
    tr = Tracer(capacity=128).configure(enabled=True)
    tr.counter("mem/hbm_bytes_in_use", TPU_0=100, TPU_1=150)
    tr.counter("mem/hbm_bytes_in_use", TPU_0=300, TPU_1=50)
    tr.counter("mem/host_rss_bytes", rss=7)
    dump = json.loads(json.dumps(tr.to_chrome(), default=str))
    cs = [e for e in dump["traceEvents"] if e.get("ph") == "C"]
    assert len(cs) == 3
    first = cs[0]
    assert first["name"] == "mem/hbm_bytes_in_use"
    # args are the raw series (no injected id — it would plot as a series)
    assert first["args"] == {"TPU_0": 100, "TPU_1": 150}
    # counters never pollute the span summary
    assert tr.summary() == {}
    agg = tr.counter_series()
    assert agg["mem/hbm_bytes_in_use"]["TPU_0"] == {
        "last": 300.0, "max": 300.0, "p95": 300.0, "p99": 300.0, "count": 2}
    assert agg["mem/hbm_bytes_in_use"]["TPU_1"] == {
        "last": 50.0, "max": 150.0, "p95": 150.0, "p99": 150.0, "count": 2}
    lines = tr.prometheus_lines(prefix="mem/")
    assert any('counter="mem/hbm_bytes_in_use",series="TPU_0",stat="max"'
               in ln and ln.endswith(" 300") for ln in lines)
    # disabled tracer: counter is a no-op
    tr.configure(enabled=False)
    tr.counter("mem/hbm_bytes_in_use", TPU_0=999)
    assert tr.counter_series()["mem/hbm_bytes_in_use"]["TPU_0"]["last"] == 300.0


def test_sampler_phases_watermarks_and_report():
    class FakeDev:
        def __init__(self, name, in_use, peak, limit):
            self._n, self._s = name, {"bytes_in_use": in_use,
                                      "peak_bytes_in_use": peak,
                                      "bytes_limit": limit}

        def __str__(self):
            return self._n

        def memory_stats(self):
            return self._s

    tr = Tracer(capacity=128).configure(enabled=True)
    stats = {"in_use": 100, "peak": 120}
    devices = lambda: [FakeDev("TPU_0", stats["in_use"], stats["peak"], 1000)]
    s = MemorySampler(tracer=tr, window=16, devices_fn=devices)
    s.sample(step=0, phase="init")
    stats.update(in_use=400, peak=450)
    s.sample(step=1, phase="first_step")
    stats.update(in_use=380, peak=460)
    s.sample(step=2, phase="steady")
    s.sample(step=3)                     # stays in steady
    wm = s.watermarks()
    assert wm["init"]["hbm_peak_bytes"] == 120
    assert wm["first_step"]["hbm_peak_bytes"] == 450
    assert wm["steady"] == {"hbm_bytes_in_use": 380, "hbm_peak_bytes": 460,
                            "host_rss_bytes": wm["steady"]["host_rss_bytes"],
                            "samples": 2}
    assert wm["steady"]["host_rss_bytes"] > 0     # /proc always available
    assert s.seen("steady") and not s.seen("ckpt")
    assert s.bytes_limit() == 1000
    rep = s.report(ledger=_micro(1), source="unit.json")
    assert rep["bytes_limit"] == 1000
    assert rep["observed"]["phases"]["steady"]["hbm_peak_bytes"] == 460
    assert rep["plan"]["phases"]["steady"]["hbm_bytes"] == 10000
    assert rep["devices"]["TPU_0"]["bytes_in_use"] == 380
    # counter tracks landed in the ring for every sample
    agg = tr.counter_series()
    assert agg["mem/hbm_bytes_in_use"]["TPU_0"]["count"] == 4
    assert agg["mem/hbm_bytes_limit"]["TPU_0"]["last"] == 1000.0
    # tie-out rows: observed vs plan, per phase, delta computed
    rows = {r["phase"]: r for r in tie_out(rep)}
    assert rows["steady"]["plan_hbm_bytes"] == 10000
    assert rows["steady"]["observed_hbm_bytes"] == 460
    assert rows["steady"]["delta_frac"] == round(460 / 10000 - 1, 4)


# ---------------------------------------------------------------------------
# the ratchet CLI (checked-in fixtures + repo-root mem_baseline.json)
# ---------------------------------------------------------------------------
def _run_mem(*args, cwd=REPO):
    return subprocess.run([sys.executable, DSTPU, "mem", *args],
                          cwd=cwd, capture_output=True, text=True)


def test_cli_exit_matrix():
    """0 clean / 1 seeded watermark regression / 2 unreadable — against the
    CHECKED-IN fixtures and baseline (workload-scoped discovery walks up
    from the artifact to the repo root)."""
    clean = _run_mem(str(FIXTURES / "mem_micro.json"))
    assert clean.returncode == 0, clean.stderr
    assert "REGRESSION" not in clean.stderr
    assert "steady" in clean.stdout          # tie-out table rendered
    # the regressed fixture is the same workload with steady peak * 3;
    # explicit --baseline compares regardless of its filename
    reg = _run_mem(str(FIXTURES / "mem_micro_regressed.json"),
                   "--baseline", str(REPO / MEM_BASELINE_NAME))
    assert reg.returncode == 1, reg.stderr
    assert "REGRESSION: steady hbm_peak_bytes" in reg.stderr
    bad = _run_mem("/etc/hostname")
    assert bad.returncode == 2


def test_cli_discovered_other_workload_skips(tmp_path):
    """A DISCOVERED baseline of another workload must not fabricate a
    verdict (plan-ledger contract)."""
    rep = json.load(open(FIXTURES / "mem_micro.json"))
    rep["source"] = "other_workload.json"
    art = tmp_path / "other_workload.json"
    art.write_text(json.dumps(rep))
    (tmp_path / MEM_BASELINE_NAME).write_text(
        (REPO / MEM_BASELINE_NAME).read_text())
    out = _run_mem(str(art))
    assert out.returncode == 0
    assert "comparison skipped" in out.stderr


def test_cli_write_baseline_ratchet(tmp_path):
    """Improvements are STALE entries expired only via --write-baseline;
    the rewrite keeps the stored tolerance (the ratchet contract)."""
    rep = json.load(open(FIXTURES / "mem_micro.json"))
    art = tmp_path / "mem_micro.json"
    art.write_text(json.dumps(rep))
    first = _run_mem(str(art), "--write-baseline", "--tolerance", "1.5")
    assert first.returncode == 0
    bl = json.load(open(tmp_path / MEM_BASELINE_NAME))
    assert bl["tolerance"] == 1.5 and bl["workload"] == "mem_micro.json"
    # improve steady by 10x -> stale note, still exit 0
    improved = json.loads(json.dumps(rep))
    for m in ("hbm_peak_bytes", "hbm_bytes_in_use"):
        improved["observed"]["phases"]["steady"][m] //= 10
    art.write_text(json.dumps(improved))
    out = _run_mem(str(art))
    assert out.returncode == 0
    assert "stale baseline entry" in out.stderr
    # expire via --write-baseline: tolerance 1.5 preserved, entry ratcheted
    _run_mem(str(art), "--write-baseline")
    bl2 = json.load(open(tmp_path / MEM_BASELINE_NAME))
    assert bl2["tolerance"] == 1.5
    assert bl2["entries"]["steady"]["hbm_peak_bytes"] == \
        improved["observed"]["phases"]["steady"]["hbm_peak_bytes"]
    # and the old (regressed-relative-to-new) numbers now fail
    art.write_text(json.dumps(rep))
    assert _run_mem(str(art)).returncode == 1


def test_check_mem_baseline_floor():
    """Sub-floor deltas are noise, not regressions."""
    rep = {"observed": {"phases": {"steady": {
        "hbm_peak_bytes": 3000, "host_rss_bytes": 0}}}}
    base = {"version": 1, "tolerance": 1.25, "min_abs_bytes": 1 << 20,
            "entries": {"steady": {"hbm_peak_bytes": 1000,
                                   "host_rss_bytes": 0}}}
    regs, stale = check_mem_baseline(rep, base)
    assert regs == [] and stale == []        # 3x but only 2000 bytes
    base["min_abs_bytes"] = 100
    regs, _ = check_mem_baseline(rep, base)
    assert len(regs) == 1 and regs[0]["ratio"] == 3.0


# ---------------------------------------------------------------------------
# preflight: analytic plan vs device limit + the offload ladder
# ---------------------------------------------------------------------------
def test_preflight_and_offload_ladder(tmp_path):
    led = _micro(0)                          # steady = 16000 bytes
    assert preflight(led, 20000)["fits"]
    verdict = preflight(led, 10000)
    assert not verdict["fits"]
    assert verdict["worst_phase"] in ("first_step", "steady", "ckpt")
    assert verdict["suggestion"]["overrides"] == {
        "zero_optimization": {"stage": 1}}   # shard first: free
    # ladder order once sharding is exhausted
    assert next_offload_tier(_micro(3))["overrides"] == {
        "zero_optimization": {"offload_optimizer": {"device": "cpu"}}}
    assert next_offload_tier(
        _micro(3, offload_optimizer="cpu"))["overrides"] == {
        "zero_optimization": {"offload_param": {"device": "cpu"}}}
    assert "nvme" in next_offload_tier(
        _micro(3, offload_optimizer="cpu",
               offload_param="cpu"))["suggestion"]
    # the CLI mode: exit 1 + suggestion when the plan cannot fit
    cfg = tmp_path / "ds_config.json"
    cfg.write_text(json.dumps({"zero_optimization": {"stage": 0},
                               "mesh": {"fsdp": 4}}))
    out = subprocess.run(
        [sys.executable, DSTPU, "mem", "--preflight", str(cfg),
         "--params", "1000000000", "--bytes-limit", "8000000000"],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 1
    assert "DOES NOT FIT" in out.stderr and "suggestion" in out.stderr
    fits = subprocess.run(
        [sys.executable, DSTPU, "mem", "--preflight", str(cfg),
         "--params", "1000", "--bytes-limit", "8000000000"],
        cwd=REPO, capture_output=True, text=True)
    assert fits.returncode == 0


def test_engine_preflight_refuse(monkeypatch):
    """memory.preflight: refuse raises at init when the plan cannot fit —
    the limit is monkeypatched in (CPU devices report no allocator
    stats)."""
    from deepspeed_tpu.accelerator.cpu_accelerator import CPUAccelerator
    from deepspeed_tpu.telemetry.memory import MemoryPreflightError
    monkeypatch.setattr(
        CPUAccelerator, "memory_stats",
        lambda self: {"TPU_0": {"bytes_in_use": 0, "peak_bytes_in_use": 0,
                                "bytes_limit": 10_000}})
    with pytest.raises(MemoryPreflightError) as exc_info:
        _engine(extra={"memory": {"enabled": True, "preflight": "refuse"}})
    assert "next tier" in str(exc_info.value)
    # warn (default) constructs fine under the same impossible limit
    eng = _engine(extra={"memory": {"enabled": True}})
    assert eng._mem_sampler is not None


# ---------------------------------------------------------------------------
# live engine: phases, report round-trip, traced counter tracks
# ---------------------------------------------------------------------------
def test_engine_phases_and_report_roundtrip(tmp_path):
    configure_tracing(enabled=True)
    try:
        eng = _engine(extra={"memory": {"enabled": True}})
        for step in range(2):
            eng.train_batch(batch=random_batch(8, seed=step))
        eng.save_checkpoint(str(tmp_path / "ckpt"))
        wm = eng._mem_sampler.watermarks()
        # every lifecycle bucket observed, even in a 2-step sync run
        assert {"init", "first_step", "steady", "ckpt"} <= set(wm)
        assert eng._param_count() > 0
        led = eng.memory_ledger()
        assert led.num_params == eng._param_count()
        art = tmp_path / "mem_report.json"
        rep = eng.dump_memory_report(str(art))
        assert rep["observed"]["phases"].keys() == wm.keys()
        # artifact round-trips through the CLI (no baseline in tmp: rc 0)
        out = _run_mem(str(art), cwd=tmp_path)
        assert out.returncode == 0, out.stderr
        assert "init" in out.stdout
    finally:
        configure_tracing(enabled=False)


def test_async_first_step_bucket_survives_drain_lag(tmp_path):
    """Async mode samples only at drains (up to sync_every steps after
    step 0): the first_step bucket must still get its observation instead
    of being overwritten to steady before any sample lands."""
    configure_tracing(enabled=True)
    try:
        eng = _engine(extra={"memory": {"enabled": True},
                             "async_pipeline": {"enabled": True,
                                                "sync_every": 4}})
        for s in range(10):
            eng.train_batch(batch=random_batch(8, seed=s))
        eng.flush_metrics()
        wm = eng._mem_sampler.watermarks()
        assert {"init", "first_step", "steady"} <= set(wm)
        assert wm["first_step"]["samples"] >= 1
    finally:
        configure_tracing(enabled=False)


def test_trace_env_dumps_counter_tracks(tmp_path):
    """Acceptance: a micro run under DSTPU_TRACE dumps Chrome-trace counter
    ("ph":"C") memory tracks alongside the existing spans."""
    trace = tmp_path / "trace.json"
    code = (
        "import deepspeed_tpu\n"
        "from deepspeed_tpu.models.simple import SimpleModel, random_batch\n"
        "engine, _, _, _ = deepspeed_tpu.initialize(\n"
        "    model=SimpleModel(hidden_dim=16),\n"
        "    config={'train_micro_batch_size_per_gpu': 1},\n"
        "    example_batch=random_batch(4))\n"
        "for s in range(2):\n"
        "    engine.train_batch(batch=random_batch(\n"
        "        engine.train_batch_size, seed=s))\n")
    env = dict(os.environ, DSTPU_TRACE=str(trace), JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO))
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    dump = json.load(open(trace))
    phs = {e.get("ph") for e in dump["traceEvents"]}
    assert "C" in phs and "X" in phs
    counters = {e["name"] for e in dump["traceEvents"]
                if e.get("ph") == "C"}
    assert "mem/host_rss_bytes" in counters   # CPU: no HBM stats, RSS rides
    spans = {e["name"] for e in dump["traceEvents"] if e.get("ph") == "X"}
    assert "engine/dispatch" in spans


# ---------------------------------------------------------------------------
# OOM forensics: chaos drill + engine classification
# ---------------------------------------------------------------------------
def test_chaos_oom_bundle_drill(tmp_path):
    """RESOURCE_EXHAUSTED -> diagnostic bundle with ledger + samples +
    per-phase deltas + trace tail, then the error re-raises (an OOM is a
    config problem, not a restartable fault)."""
    from deepspeed_tpu.resilience.chaos import (ChaosConfig,
                                                ChaosInjectedOOMError,
                                                ChaosMonkey)
    from deepspeed_tpu.resilience.runner import FaultTolerantRunner
    configure_tracing(enabled=True)
    try:
        eng = _engine(extra={
            "memory": {"enabled": True},
            "resilience": {"diagnostics_dir": str(tmp_path / "diag")}})
        runner = FaultTolerantRunner(
            eng, save_dir=str(tmp_path / "ckpt"),
            chaos=ChaosMonkey(ChaosConfig(oom_step=2)))
        with pytest.raises(ChaosInjectedOOMError):
            runner.run(num_steps=5,
                       batch_fn=lambda s: random_batch(8, seed=s))
        runner.close()
        assert runner.chaos.injected["oom"] == 1
        bundle = tmp_path / "diag" / "oom_step2"
        assert bundle.is_dir()
        diag = json.load(open(bundle / "diag.json"))
        assert diag["reason"] == "oom"
        assert "RESOURCE_EXHAUSTED" in diag["error"]
        mem = diag["memory"]
        assert mem["ledger"]["inputs"]["num_params"] == eng._param_count()
        assert len(mem["samples"]) >= 1
        assert "plan_vs_observed_delta_frac" in mem
        assert set(mem["watermarks"]) >= {"init", "first_step"}
        # the trace tail rides in the bundle, Perfetto-loadable
        tail = json.load(open(bundle / "trace_tail.json"))
        names = {e.get("name") for e in tail["traceEvents"]}
        assert "chaos/oom" in names
    finally:
        configure_tracing(enabled=False)


def test_engine_note_oom_stashes_forensics():
    configure_tracing(enabled=True)
    try:
        eng = _engine(extra={"memory": {"enabled": True}})
        eng.train_batch(batch=random_batch(8, seed=0))
        eng._note_oom(RuntimeError("deadline exceeded"))
        assert eng.last_oom is None              # non-OOM: untouched
        eng._note_oom(RuntimeError(
            "RESOURCE_EXHAUSTED: out of memory allocating 16G"))
        assert eng.last_oom is not None
        assert eng.last_oom["ledger"]["inputs"]["zero_stage"] == 0
        assert get_tracer().instant_counts().get("mem/oom", 0) >= 1
    finally:
        configure_tracing(enabled=False)


# ---------------------------------------------------------------------------
# satellites: see_memory_usage, autotuner capture, serving reconciliation
# ---------------------------------------------------------------------------
def test_see_memory_usage_noop_is_jax_free(monkeypatch):
    """force=False must return before ANY jax call (the old version
    imported jax first); force=True routes through the timeline."""
    import jax

    from deepspeed_tpu.utils.memory import see_memory_usage

    def boom():
        raise AssertionError("no-op path touched jax")
    monkeypatch.setattr(jax, "process_index", boom)
    assert see_memory_usage("milestone") is None       # no raise: jax-free
    monkeypatch.undo()
    configure_tracing(enabled=True)
    try:
        stats = see_memory_usage("after fwd", force=True, step=7)
        assert stats is not None and "host" in stats
        counts = get_tracer().instant_counts(prefix="mem/")
        assert counts.get("mem/see_memory_usage", 0) >= 1
    finally:
        configure_tracing(enabled=False)


def test_autotuner_oom_experiment_capture():
    """An oom-classified experiment records live stats + the candidate's
    analytic ledger + the observed peak — not just the string match."""
    from deepspeed_tpu.autotuning.scheduler import ExperimentRunner
    from deepspeed_tpu.autotuning.tuner import Experiment

    def exploding_loss(*a, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating "
                           "12.5G on TPU_0")

    runner = ExperimentRunner(
        SimpleModel(hidden_dim=16), lambda b: random_batch(b),
        {"optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
         "train_micro_batch_size_per_gpu": 2},
        loss_fn=exploding_loss, warmup_steps=1, measure_steps=1)
    exp = runner(Experiment("oom_candidate",
                            {"zero_optimization": {"stage": 2}}))
    assert exp.status == "oom"
    assert exp.memory is not None
    assert "stats" in exp.memory
    assert exp.memory["ledger"]["inputs"]["zero_stage"] == 2


class FakeEngine(ResidentEngine):
    """One block of 1 KiB held of seven, whatever is admitted; a request
    projects two."""

    def kv_usable_blocks(self):
        return 7

    def kv_reserved_blocks(self):
        return 1

    def kv_block_bytes(self):
        return 1024

    def kv_blocks_needed(self, num_tokens):
        return 2


def test_serving_kv_reconciliation():
    """Projected (admission model) vs observed (engine-reserved) KV bytes:
    gauges on /metrics, an edge-triggered drift instant, counter track."""
    from deepspeed_tpu.serving.request import Request
    from deepspeed_tpu.serving.server import InferenceServer, ServingConfig

    configure_tracing(enabled=True)
    # the counts below are of this test's events: a ring that an earlier
    # test of this process left large and full (a traced benchmark
    # rehearsal) would be counted with them
    get_tracer().clear()
    try:
        server = InferenceServer(FakeEngine(), ServingConfig())
        req = Request(uid=1, prompt_tokens=[1, 2], max_new_tokens=4)
        server._inflight[1] = req
        # projected 2 blocks * 1024 vs observed 1 * 1024 -> 50% drift
        server._reconcile_kv(projected_blocks=2)
        snap = server.metrics.snapshot()
        assert snap["kv_projected_bytes"] == 2048
        assert snap["kv_observed_bytes"] == 1024
        assert snap["kv_drift_events"] == 1
        # edge-triggered: still drifted, no second event
        server._reconcile_kv(projected_blocks=2)
        assert server.metrics.snapshot()["kv_drift_events"] == 1
        # convergence clears the edge; a new divergence fires again
        server._reconcile_kv(projected_blocks=1)
        server._reconcile_kv(projected_blocks=2)
        assert server.metrics.snapshot()["kv_drift_events"] == 2
        assert get_tracer().instant_counts().get("serve/kv_drift") == 2
        assert get_tracer().counter_series()["serve/kv_bytes"][
            "projected"]["last"] == 2048.0
        text = server.metrics.prometheus_text()
        assert "dstpu_serving_kv_projected_bytes 2048" in text
        assert "dstpu_serving_kv_observed_bytes 1024" in text
        assert "dstpu_serving_kv_drift_events 2" in text
        # serve/ + mem/ counter families share ONE metadata block: a second
        # '# TYPE dstpu_trace_counter' line fails the whole Prometheus scrape
        get_tracer().counter("mem/host_rss_bytes", rss=7)
        text = server.metrics.prometheus_text()
        assert text.count("# TYPE dstpu_trace_counter") == 1
        assert "mem/host_rss_bytes" in text
    finally:
        configure_tracing(enabled=False)


def test_plan_reads_memory_counters():
    """dstpu plan consumes the dsmem counter tracks: headroom lands in the
    report and the proposal table escalates the offload tier when the
    observed peak is within 5% of the limit."""
    from deepspeed_tpu.telemetry.attribution import (attribute,
                                                     events_from_chrome)
    # short dispatch spans with long gaps: a residual-dominant sync window
    # (the raise_micro_batch trigger) under the window-split threshold
    events = [
        {"name": "engine/dispatch", "ph": "X", "ts": i * 1000.0,
         "dur": 100.0, "tid": 1, "cat": "train", "args": {"step": i}}
        for i in range(4)
    ] + [
        {"name": "mem/hbm_bytes_in_use", "ph": "C", "ts": 500.0, "tid": 1,
         "args": {"TPU_0": 9_700}},
        {"name": "mem/hbm_bytes_limit", "ph": "C", "ts": 500.0, "tid": 1,
         "args": {"TPU_0": 10_000}},
    ]
    report = attribute(events_from_chrome(events), source="synthetic")
    memory = report["memory"]
    assert memory["devices"]["TPU_0"]["peak_bytes_in_use"] == 9700
    assert memory["min_headroom_frac"] == 0.03
    ids = [p["id"] for p in report["proposals"]]
    assert "raise_offload_tier" in ids
    assert "raise_micro_batch" not in ids    # <10% headroom: yields
    # with ample headroom the offload rule stays quiet and micro-batch
    # advice carries the observed number
    events[-2]["args"]["TPU_0"] = 4_000
    report = attribute(events_from_chrome(events), source="synthetic")
    ids = {p["id"]: p for p in report["proposals"]}
    assert "raise_offload_tier" not in ids
    assert "raise_micro_batch" in ids
    assert ids["raise_micro_batch"]["predicted"]["hbm_headroom_frac"] == 0.6


def test_env_report_memory_rows():
    from deepspeed_tpu.env_report import memory_report
    rows = dict(memory_report())
    assert "mem ledger" in rows
    assert rows["mem baseline"].startswith("4 phases ratcheted")


# ---------------------------------------------------------------------------
# the dslint proof: the sampler never host-syncs
# ---------------------------------------------------------------------------
def test_sampler_stays_inside_the_hot_taint(package_callgraph, hot_reached):
    g = package_callgraph
    for fn in ("on_drain", "sample", "_collect"):
        key = g.resolve("deepspeed_tpu/telemetry/memory.py",
                        f"MemorySampler.{fn}")
        assert key is not None, f"MemorySampler.{fn} gone"
        assert key in hot_reached, f"{fn} fell out of the hot taint"


def test_fixtures_regenerate_clean(tmp_path, monkeypatch):
    """Fixtures + baseline are ONE artifact set: the regeneration script's
    output matches what is checked in (drift here means someone changed
    the ledger math without re-running make_fixtures.py)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mem_make_fixtures", FIXTURES / "make_fixtures.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mem = mod._load_memory()
    fresh = mod.build_clean_report(mem)
    checked_in = json.load(open(FIXTURES / "mem_micro.json"))
    assert fresh == checked_in
