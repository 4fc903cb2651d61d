"""The async serve core (PR 18): the extracted host-orchestration
scheduler (``runtime/sched.py``) and decode-first chunked prefill.

Proof obligations, all deterministic counters (no wall-clock judgments):

  * DispatchRing/StagedPrefetcher/TickLedger units — drain semantics,
    anchor windows, bounded-queue overflow accounting, identity-keyed
    prefetch lifecycle, ceil-div decode-gap arithmetic
  * chunked-prefill bit-parity: a prompt prefilled in k capped chunks
    generates EXACTLY the single-shot tokens, composed with prefix-cache
    hits and speculative decoding (``speculative_k > 0``)
  * `serving.scheduler` off => bit-identical pre-PR planning (the config
    group defaults pin) and chunk shapes add ZERO compiles after warmup
    (chunk buckets stay inside the compile-ledger ladder)
  * the seeded ``long_prompt`` A/B: every chunked tick's prefill tokens
    <= cap, the worst decode gap strictly smaller than unchunked over the
    SAME seeded arrivals (common gap-unit normalizer), and the
    ``prefill_chunk_tokens`` plan rule verifies end-to-end
    (plan -> verify -> VERIFIED persisted under plan.serve_verifications)
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.ragged_manager import StateManager
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig, plan_step
from deepspeed_tpu.models.llama import (TINY_LLAMA, LlamaConfig,
                                        LlamaForCausalLM)
from deepspeed_tpu.runtime.sched import (DispatchRing, StagedPrefetcher,
                                         TickLedger)
from deepspeed_tpu.telemetry.compiles import compiles_total
from deepspeed_tpu.telemetry.tracer import get_tracer

pytestmark = pytest.mark.sched


# ---------------------------------------------------------------------------
# the extracted core: DispatchRing / StagedPrefetcher / TickLedger units
# ---------------------------------------------------------------------------
def test_dispatch_ring_cadence_and_drain():
    ring = DispatchRing(sync_every=3)
    assert ring.drain() is None                      # nothing pending
    assert not ring.push({"x": jnp.float32(0.0)})
    assert not ring.push({"x": jnp.float32(1.0)})
    assert ring.push({"x": jnp.float32(2.0)})        # cadence reached
    assert len(ring) == 3
    res = ring.drain(extra=jnp.float32(7.0))
    assert len(ring) == 0
    assert [float(p["x"]) for p in res.payloads] == [0.0, 1.0, 2.0]
    assert float(res.extra) == 7.0
    assert not res.anchored and res.window_s == 0.0  # never armed


def test_dispatch_ring_anchor_window():
    ring = DispatchRing()
    ring.rearm_if_idle()                 # empty -> anchors
    assert ring.anchor is not None
    anchor = ring.anchor
    ring.push({"x": jnp.float32(0.0)})
    ring.rearm_if_idle()                 # pending -> must NOT re-anchor
    assert ring.anchor == anchor
    res = ring.drain()
    assert res.anchored and res.window_s >= 0.0
    # drain does NOT consume the anchor (the producer re-arms at the next
    # idle dispatch); reset_anchor un-arms explicitly
    assert ring.anchor == anchor
    ring.reset_anchor()
    ring.push({"x": jnp.float32(1.0)})
    assert not ring.drain().anchored


def test_dispatch_ring_store_take_requeue_overflow():
    ring = DispatchRing(capacity=4)
    assert ring.store([{"i": i} for i in range(3)]) == 0
    # 3 queued + 3 more > maxlen 4: the deque evicts the 2 OLDEST entries
    # (warned — the return value is the accounting the warning reports)
    assert ring.store([{"i": i} for i in range(3, 6)]) == 2
    taken = ring.take()
    assert [e["i"] for e in taken] == [2, 3, 4, 5]
    assert ring.take() == []
    # requeue restores original order at the front...
    ring.store([{"i": 9}])
    ring.requeue(taken[:2])
    assert [e["i"] for e in ring.take()] == [2, 3, 9]
    # ...and refuses to evict NEWER entries: with 3 slots free only the
    # first 3 requeued entries land, the tail is dropped (warned)
    ring.store([{"i": 0}])
    ring.requeue([{"i": i} for i in range(10, 14)])
    assert [e["i"] for e in ring.take()] == [10, 11, 12, 0]


class _FakeLoader:
    def __init__(self):
        self.closed = False

    def close(self):
        self.closed = True


def test_staged_prefetcher_identity_keyed():
    staged = StagedPrefetcher(depth=2)
    src_a, src_b = object(), object()
    a = staged.ensure(src_a, _FakeLoader)
    assert staged.ensure(src_a, _FakeLoader) is a    # stable identity
    assert staged.switches == 0
    b = staged.ensure(src_b, _FakeLoader)            # churn: close + rebuild
    assert b is not a and a.closed and not b.closed
    assert staged.switches == 1
    staged.close()
    assert b.closed and staged.loader is None
    staged.close()                                   # idempotent


def test_tick_ledger_counters_and_gap():
    led = TickLedger()
    led.observe_tick(64, 1, 0, cap=0)           # pure prefill tick
    led.observe_tick(63, 1, 1, cap=0)           # decode stalled behind 63
    led.observe_tick(0, 0, 4, cap=0)            # pure decode tick
    assert (led.ticks, led.prefill_ticks, led.decode_ticks) == (3, 2, 2)
    snap = led.snapshot(gap_unit_tokens=16)
    assert snap["max_prefill_tokens_per_tick"] == 64
    assert snap["max_decode_stall_tokens"] == 63    # the 64 ran no decode
    assert snap["max_decode_gap_ticks"] == 4        # ceil(63 / 16)
    assert snap["chunk_tokens_total"] == 127
    # the window resets maxima, not totals
    led.reset_window()
    led.observe_tick(32, 1, 2, cap=32)
    snap = led.snapshot(cap=32)
    assert snap["max_prefill_tokens_per_tick"] == 32
    assert snap["decode_gap_unit_tokens"] == 32     # cap is the unit
    assert snap["max_decode_gap_ticks"] == 1
    assert snap["chunk_tokens_total"] == 159        # cumulative survived
    assert snap["capped_chunk_ticks"] == 1
    assert snap["prefill_cap_utilization"] == 1.0


# ---------------------------------------------------------------------------
# the tick planner: chunk cap + block snapping; cap off == pre-PR planning
# ---------------------------------------------------------------------------
def _planner_state():
    sm = StateManager()
    sm.create(1, np.arange(90) % 100)            # long prompt mid-prefill
    dec = sm.create(2, [1, 2, 3])
    dec.seen_tokens = 3
    dec.generated.append(7)
    return sm


def test_plan_step_chunk_cap_and_block_snap():
    sm = _planner_state()
    cfg = SchedulerConfig(max_tokens_per_step=64, prefill_buckets=(16, 32, 64),
                          prefill_chunk_tokens=24)
    plan = plan_step(sm.decoding(), sm.prefilling(), cfg, block_tokens=16)
    assert [s.uid for s in plan.decode_seqs] == [2]  # decode-first
    chunk = plan.prefill_chunks[0]
    # 24-token cap snapped DOWN to the 16-token KV block boundary: a
    # mid-prompt chunk may never end inside a block (the next chunk would
    # re-open a partially-filled page)
    assert chunk.length == 16 and chunk.length % 16 == 0
    assert chunk.bucket == 16
    # the FINAL chunk of a prompt may end mid-block (normal tail)
    seq = sm.get(1)
    seq.seen_tokens = 80
    plan = plan_step(sm.decoding(), sm.prefilling(), cfg, block_tokens=16)
    assert plan.prefill_chunks[0].length == 10


def test_plan_step_cap_off_bit_identical():
    """`serving.scheduler` off (cap=0) => the planner output is EXACTLY the
    pre-PR plan, block_tokens or not — the config group defaults to
    today's semantics."""
    def plans(cfg, block_tokens):
        sm = _planner_state()
        p = plan_step(sm.decoding(), sm.prefilling(), cfg,
                      block_tokens=block_tokens)
        return ([s.uid for s in p.decode_seqs],
                [(c.seq.uid, c.start, c.length, c.bucket)
                 for c in p.prefill_chunks])

    legacy = SchedulerConfig(max_tokens_per_step=64,
                             prefill_buckets=(16, 32, 64))
    assert legacy.prefill_chunk_tokens == 0          # the default IS off
    assert plans(legacy, 0) == plans(legacy, 16) == plans(
        dataclasses.replace(legacy, prefill_chunk_tokens=0), 16)


# ---------------------------------------------------------------------------
# live-engine parity: chunked == single-shot, composed with prefix + spec
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model_and_params():
    cfg = LlamaConfig(**{**TINY_LLAMA.__dict__, "dtype": jnp.float32,
                         "max_seq_len": 512})
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return cfg, model, params


def _make_engine(params, cfg, spec_k=0):
    return InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=64,
        scheduler=SchedulerConfig(max_tokens_per_step=64,
                                  prefill_buckets=(16, 32, 64)),
        speculative_k=spec_k))


def test_chunked_prefill_bit_parity(model_and_params):
    cfg, _model, params = model_and_params
    prompt = list(np.random.default_rng(3).integers(0, cfg.vocab_size, 90))
    plain = _make_engine(params, cfg).generate(prompt, max_new_tokens=6)

    eng = _make_engine(params, cfg)
    eng.configure_chunked_prefill(32)
    chunked = eng.generate(prompt, max_new_tokens=6)
    assert chunked == plain
    # the ledger proves it WAS chunked, every chunk under the cap, and
    # chunk conservation: exactly the prompt's tokens went through chunks
    snap = eng.sched_stats()
    assert snap["chunks_total"] >= 3
    assert snap["max_prefill_tokens_per_tick"] <= 32
    assert snap["chunk_tokens_total"] == len(prompt)


def test_chunked_prefill_validation(model_and_params):
    cfg, _model, params = model_and_params
    eng = _make_engine(params, cfg)
    with pytest.raises(ValueError, match="block"):
        eng.configure_chunked_prefill(8)     # 0 < cap < kv block size
    eng.configure_chunked_prefill(16)
    eng.configure_chunked_prefill(0)         # 0 = disable, always legal
    assert eng.config.scheduler.prefill_chunk_tokens == 0


def test_chunked_prefill_with_prefix_cache(model_and_params):
    """Chunking composes with prefix-cache hits: the chunk planner sees
    only the post-hit remainder and the tokens stay bit-identical."""
    cfg, _model, params = model_and_params
    rng = np.random.default_rng(4)
    shared = list(rng.integers(0, cfg.vocab_size, 48))
    tail_a = list(rng.integers(0, cfg.vocab_size, 20))
    tail_b = list(rng.integers(0, cfg.vocab_size, 24))

    def run(chunk_cap):
        eng = _make_engine(params, cfg)
        eng.enable_prefix_cache(32)
        if chunk_cap:
            eng.configure_chunked_prefill(chunk_cap)
        out = [eng.generate(shared + tail_a, max_new_tokens=4, uid=1),
               eng.generate(shared + tail_b, max_new_tokens=4, uid=2)]
        return out, eng.prefix_stats(), eng.sched_stats()

    plain, _stats0, _snap0 = run(0)
    chunked, stats, snap = run(32)
    assert chunked == plain
    assert stats["prefix_hit_tokens"] >= 48          # the hit happened
    assert snap["max_prefill_tokens_per_tick"] <= 32
    # conservation THROUGH the cache: chunks carried exactly the computed
    # (post-hit) tokens, not the full prompts
    assert snap["chunk_tokens_total"] == stats["prefill_tokens_computed"]
    assert snap["chunk_tokens_total"] < len(shared) * 2 + len(tail_a) + \
        len(tail_b)


def test_chunked_prefill_with_speculative(model_and_params):
    cfg, _model, params = model_and_params
    prompt = list(np.random.default_rng(5).integers(0, cfg.vocab_size, 70))
    plain = _make_engine(params, cfg).generate(prompt, max_new_tokens=12)

    eng = _make_engine(params, cfg, spec_k=4)
    eng.configure_chunked_prefill(32)
    spec = eng.generate(prompt, max_new_tokens=12)
    assert spec[:len(plain)] == plain
    assert eng.sched_stats()["max_prefill_tokens_per_tick"] <= 32


def test_chunked_shapes_zero_compiles_after_warmup(model_and_params):
    """The compile-ledger gate: chunk boundaries snap to the bucket ladder
    and KV blocks, so turning the cap ON adds ZERO XLA compiles once the
    unchunked shapes are warm — no mid-siege compiles."""
    cfg, _model, params = model_and_params
    prompt = list(np.random.default_rng(6).integers(0, cfg.vocab_size, 90))
    warm = _make_engine(params, cfg)
    warm_tokens = warm.generate(prompt, max_new_tokens=6)   # pays compiles

    mark = compiles_total()
    eng = _make_engine(params, cfg)
    eng.configure_chunked_prefill(32)
    assert eng.generate(prompt, max_new_tokens=6) == warm_tokens
    assert compiles_total() - mark == 0


# ---------------------------------------------------------------------------
# the serving.scheduler config group
# ---------------------------------------------------------------------------
def test_serving_scheduler_group_validation():
    from deepspeed_tpu.serving.server import SCHEDULER_DEFAULTS, ServingConfig
    assert ServingConfig().scheduler == SCHEDULER_DEFAULTS
    # partial dicts merge over the defaults (config-file ergonomics)
    cfg = ServingConfig(scheduler={"prefill_chunk_tokens": 32})
    assert cfg.scheduler["prefill_chunk_tokens"] == 32
    with pytest.raises(ValueError, match="unknown"):
        ServingConfig(scheduler={"chunk_cap": 32})
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ServingConfig(scheduler={"prefill_chunk_tokens": -1})
    # the role-split engine pair went with PR 33: its key is unknown now
    with pytest.raises(ValueError, match="unknown"):
        ServingConfig(scheduler={"role_split": True})


def test_scheduler_defaults_pinned_across_modules():
    """serve_attribution carries a literal copy of the scheduler defaults
    (it must load standalone on jax-less hosts) — pin the copies equal so
    drift between the planner's fallback and the server is impossible."""
    from deepspeed_tpu.serving.server import SCHEDULER_DEFAULTS
    from deepspeed_tpu.telemetry.serve_attribution import SERVING_DEFAULTS
    assert SERVING_DEFAULTS["scheduler"] == SCHEDULER_DEFAULTS


# ---------------------------------------------------------------------------
# the seeded long_prompt A/B + the plan->verify acceptance drill
# ---------------------------------------------------------------------------
def _long_prompt(num_requests=12):
    from deepspeed_tpu.serving import bench_serve
    return dataclasses.replace(bench_serve.SCENARIOS["long_prompt"],
                               num_requests=num_requests)


def _run_long_prompt(serving_overrides):
    from deepspeed_tpu.serving import bench_serve
    server = bench_serve.build_tiny_server(
        serving_overrides=serving_overrides).start()
    try:
        return bench_serve.run_scenario(server, _long_prompt())
    finally:
        server.stop(drain_timeout=30.0)


def test_long_prompt_decode_gap_ab_proof():
    """The tentpole's acceptance inequalities over the SAME seeded
    arrivals: chunked ticks never exceed the cap, the worst decode gap is
    STRICTLY smaller than unchunked (common 32-token normalizer), chunk
    conservation holds in both modes, and — the run being second in the
    process — chunking adds zero mid-measurement compiles."""
    cap = 32
    base = _run_long_prompt(None)
    chunk = _run_long_prompt({"scheduler": {"prefill_chunk_tokens": cap}})
    b, c = base["scheduler"], chunk["scheduler"]

    assert b["prefill_chunk_tokens"] == 0 and c["prefill_chunk_tokens"] == cap
    # every chunked tick bounded by the cap; unchunked proves the workload
    # genuinely produced over-cap ticks to cut
    assert c["max_prefill_tokens_per_tick"] <= cap
    assert b["max_prefill_tokens_per_tick"] > cap
    # the decode-gap A/B in COMMON units (ceil of stall tokens / cap)
    base_gap = -(-b["max_decode_stall_tokens"] // cap)
    assert c["max_decode_gap_ticks"] < base_gap, (c, b)
    assert c["decode_gap_unit_tokens"] == cap
    # conservation: chunking moved exactly the tokens prefill computed
    assert b["chunk_conservation_ok"] and c["chunk_conservation_ok"]
    assert c["chunk_tokens_total"] == b["chunk_tokens_total"]
    assert c["prefill_cap_utilization"] > 0.5       # the cap binds
    # the chunked run rides shapes the unchunked run already compiled
    assert chunk["counters"]["compiles_during_measurement"] == 0
    # the counter the plan rule predicates on is mirrored into counters
    assert chunk["counters"]["max_prefill_tokens_per_tick"] == \
        c["max_prefill_tokens_per_tick"]
    states = chunk["requests"]["states"]
    assert states.get("finished", 0) == 12, states


@pytest.mark.parametrize("prefill_us,fires", [(3_400, False), (3_600, True)])
def test_chunk_rule_fires_at_the_prefill_share_threshold(prefill_us, fires):
    """The rule's one host-time condition, on a hand-built 10 ms tick: the
    prefill stage must hold `PREFILL_DOMINANT_SHARE` of it, with a decode
    in flight and a counted worst tick."""
    from deepspeed_tpu.telemetry import serve_attribution as sa

    def ev(name, ts, dur):
        return sa.Ev(name, "serve", "X", ts, dur, 1, {"tick": 1})
    report = sa.attribute_serve(
        [ev("serve/tick", 0, 10_000),
         ev("serve/step_prefill", 100, prefill_us),
         ev("serve/step_decode", 5_000, 1_000)],
        meta={"bench_counters": {"max_prefill_tokens_per_tick": 96}})
    assert sa.PREFILL_DOMINANT_SHARE == 0.35
    ids = [p["id"] for p in report["proposals"]]
    assert ("prefill_chunk_tokens" in ids) == fires


def test_long_prompt_chunk_proposal_verify_loop(tmp_path, monkeypatch):
    """Acceptance drill: the seeded long_prompt preset trips the
    `prefill_chunk_tokens` rule (prefill ticks with decodes in flight),
    `--verify-plan` re-runs the SAME preset with the proposed cap, and the
    `max_prefill_tokens_per_tick <= cap` prediction holds EXACTLY —
    VERIFIED, persisted under plan.serve_verifications."""
    from deepspeed_tpu.autotuning.serve_verify import verify_serve_plan
    from deepspeed_tpu.serving import bench_serve
    from deepspeed_tpu.telemetry import serve_attribution as sa

    # The drill is the loop from proposal to verdict, and everything it
    # asserts below follows from counters. The rule's share threshold is a
    # share of HOST time: with six test workers on the machine, and by
    # whether this process had compiled the prefill shapes before, it read
    # anywhere from 0.3 to 0.9 on one tree. It is taken out of the drill
    # (any prefill share trips the rule here) and pinned on hand-built
    # ticks by the test above.
    monkeypatch.setattr(sa, "PREFILL_DOMINANT_SHARE", 0.0)
    builder = {"kv_num_blocks": 64, "kv_block_size": 16}
    # near-max prompts, short decodes: every tick of the preset carries
    # prefill, and decodes are in flight behind it
    scenario = dataclasses.replace(_long_prompt(), prompt_len=(80, 96),
                                   max_new_tokens=(4, 6))
    warm = bench_serve.build_tiny_server(**builder).start()
    try:
        bench_serve.run_scenario(
            warm, dataclasses.replace(scenario, num_requests=4))
    finally:
        warm.stop(drain_timeout=30.0)
    tracer = get_tracer()
    tracer.clear()
    tracer.configure(enabled=True)
    server = bench_serve.build_tiny_server(**builder).start()
    try:
        report = bench_serve.run_scenario(server, scenario, provenance={
            "builder": builder, "trace_path": "long_prompt_trace.json"})
    finally:
        server.stop(drain_timeout=30.0)
    tracer.export_chrome(str(tmp_path / "long_prompt_trace.json"))
    tracer.configure(enabled=False)
    report_path = tmp_path / "long_prompt_report.json"
    report_path.write_text(json.dumps(report, default=str))

    plan = sa.analyze_serve_path(str(report_path))
    chunk_props = [p for p in plan["proposals"]
                   if p["id"] == "prefill_chunk_tokens"]
    assert chunk_props, [p["id"] for p in plan["proposals"]]
    prop = chunk_props[0]
    assert prop["knob"] == "scheduler.prefill_chunk_tokens"
    new_cap = prop["overrides"]["serving"]["scheduler"][
        "prefill_chunk_tokens"]
    assert new_cap >= 16 and new_cap % 16 == 0       # block-aligned
    assert prop["predicted"]["counter"] == "max_prefill_tokens_per_tick"
    assert prop["predicted"]["value"] == new_cap
    assert prop["predicted"]["baseline"] > new_cap

    # verify ONLY the chunk proposal (the drill under test)
    plan["proposals"] = chunk_props
    art = tmp_path / "serve_plan.json"
    art.write_text(json.dumps(plan, default=str))
    verdicts = verify_serve_plan(str(art), results_dir=str(tmp_path),
                                 max_proposals=1)
    get_tracer().configure(enabled=False)
    assert len(verdicts) == 1
    assert verdicts[0]["proposal"] == "prefill_chunk_tokens"
    assert verdicts[0]["verdict"] == "verified", verdicts[0]
    observed = verdicts[0]["observed"]["max_prefill_tokens_per_tick"]
    assert observed <= new_cap
    results = json.load(open(tmp_path / "autotuning_results.json"))
    assert results["plan"]["serve_verifications"] == verdicts
