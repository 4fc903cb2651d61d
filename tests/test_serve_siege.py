"""Serving-under-siege tests: the host-RAM KV offload tier, the
degradation ladder, request-level fault isolation (poison quarantine),
the serve chaos knobs, and the bench_serve overload harness.

Engines share the KV/bucket shapes of tests/test_serving.py so jit
compilations are shared across the module (XLA static shapes — one
compile per shape per process). Unit pieces (planners, ladder, chaos
parsing) run without an engine; fault-isolation and drift tests drive
``_serve_once`` manually on fake engines for exact tick control; the
acceptance drills run the real serve loop on the tiny fp32 llama.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import (TINY_LLAMA, LlamaConfig,
                                        LlamaForCausalLM)
from deepspeed_tpu.resilience.chaos import (ChaosConfig, ChaosMonkey,
                                            ChaosInjectedPoisonError)
from deepspeed_tpu.serving import (BackpressureError, DegradationLadder,
                                   InferenceServer, LadderConfig,
                                   RequestState, ServeLevel, ServingConfig)
from deepspeed_tpu.serving.kv_tier import (effective_usable_blocks,
                                           plan_demotions, plan_promotions,
                                           tier_pressure)
from deepspeed_tpu.serving.server import _EngineStepError
from deepspeed_tpu.telemetry.tracer import get_tracer
from serving_fakes import ResidentEngine

pytestmark = pytest.mark.serve_load


def _tiny_fp32():
    return LlamaConfig(**{**TINY_LLAMA.__dict__, "dtype": jnp.float32,
                          "max_seq_len": 512})


@pytest.fixture(scope="module")
def model_and_params():
    cfg = _tiny_fp32()
    model = LlamaForCausalLM(cfg)
    batch = {"input_ids": np.zeros((1, 8), np.int32)}
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    return cfg, params


KV_BLOCKS = 64  # shared with tests/test_serving.py: kv shape is a compile shape


def _engine(cfg, params, kv_blocks=KV_BLOCKS):
    return InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=kv_blocks,
        scheduler=SchedulerConfig(max_tokens_per_step=64,
                                  prefill_buckets=(16, 32, 64))))


def _tick(server):
    """One manual serve tick with the loop's fault-handling semantics —
    exact tick control for the fake-engine tests."""
    try:
        return server._serve_once()
    except _EngineStepError as e:
        server._on_step_fault(e)
        return False


# ---------------------------------------------------------------------------
# tier planners (pure arithmetic)
# ---------------------------------------------------------------------------
def test_tier_planners():
    assert effective_usable_blocks(63, 0.0) == 63
    assert effective_usable_blocks(63, 0.85) == 9
    assert effective_usable_blocks(63, 0.999) == 1

    # demote LIFO until both lines hold, never below min_active
    assert plan_demotions([2, 2, 2, 2], [2, 2, 2, 2], reserved_blocks=8,
                          capacity_blocks=100, demote_line_blocks=3,
                          min_active=1) == [3, 2, 1]
    assert plan_demotions([2, 2, 2, 2], [2, 2, 2, 2], reserved_blocks=8,
                          capacity_blocks=100, demote_line_blocks=3,
                          min_active=3) == [3]
    # capacity-line violation (chaos shrank effective usable) demotes too
    assert plan_demotions([4, 4], [1, 1], reserved_blocks=2,
                          capacity_blocks=5, demote_line_blocks=100,
                          min_active=1) == [1]
    assert plan_demotions([2, 2], [2, 2], reserved_blocks=4,
                          capacity_blocks=100, demote_line_blocks=10,
                          min_active=1) == []
    # a zero-held victim frees nothing against the demote line: skipped
    # (kept active) instead of paused for no benefit
    assert plan_demotions([2, 2, 2], [2, 0, 2], reserved_blocks=6,
                          capacity_blocks=100, demote_line_blocks=3,
                          min_active=1) == [2, 0]

    # promotion respects capacity, free blocks AND the demote line (no
    # same-tick demote->promote ping-pong)
    assert plan_promotions([2, 2], [2, 2], active_worst_sum=2,
                           capacity_blocks=10, free_blocks=10,
                           reserved_blocks=2, demote_line_blocks=8) == 2
    assert plan_promotions([2, 2], [2, 2], active_worst_sum=2,
                           capacity_blocks=10, free_blocks=10,
                           reserved_blocks=2, demote_line_blocks=3) == 0
    assert plan_promotions([2], [8], active_worst_sum=2,
                           capacity_blocks=10, free_blocks=4,
                           reserved_blocks=2, demote_line_blocks=100) == 0
    # progress guard: nothing active -> FIFO head promotes past the lines
    assert plan_promotions([20], [4], active_worst_sum=0,
                           capacity_blocks=10, free_blocks=4,
                           reserved_blocks=0, demote_line_blocks=1) == 1

    p, reason = tier_pressure(9, 10, 0, 8, 0, 0)
    assert p == pytest.approx(0.9) and reason == "device_kv"
    p, reason = tier_pressure(1, 10, 8, 8, 0, 0)
    assert p == pytest.approx(1.0) and reason == "queue"
    p, reason = tier_pressure(0, 10, 0, 8, 900, 1000)
    assert p == pytest.approx(0.9) and reason == "host_kv"


# ---------------------------------------------------------------------------
# degradation ladder (hysteresis, edges, sticky degraded)
# ---------------------------------------------------------------------------
def test_ladder_transitions_hysteresis_and_sticky():
    ladder = DegradationLadder(LadderConfig(
        brownout_pressure=0.5, shed_pressure=0.9, hysteresis=0.1,
        cooldown_ticks=3))
    assert ladder.level is ServeLevel.HEALTHY
    assert ladder.observe(0.4) is None
    # upward edges are immediate, and may jump rungs
    assert ladder.observe(0.6) == (ServeLevel.HEALTHY, ServeLevel.BROWNOUT)
    assert ladder.observe(0.95) == (ServeLevel.BROWNOUT, ServeLevel.SHED)
    # descending needs cooldown_ticks BELOW threshold - hysteresis (0.8)
    assert ladder.observe(0.85) is None          # calm zone not reached
    assert ladder.observe(0.7) is None
    assert ladder.observe(0.85) is None          # resets the calm count
    assert ladder.observe(0.7) is None
    assert ladder.observe(0.7) is None
    assert ladder.observe(0.7) == (ServeLevel.SHED, ServeLevel.BROWNOUT)
    # one rung at a time
    assert ladder.level is ServeLevel.BROWNOUT
    for _ in range(2):
        assert ladder.observe(0.1) is None
    assert ladder.observe(0.1) == (ServeLevel.BROWNOUT, ServeLevel.HEALTHY)
    assert ladder.entries["brownout"] == 2 and ladder.entries["shed"] == 1

    # degraded is sticky: pressure can neither cause nor clear it
    assert ladder.latch_degraded("engine fault") == (
        ServeLevel.HEALTHY, ServeLevel.DEGRADED)
    assert ladder.observe(0.0) is None
    assert ladder.level is ServeLevel.DEGRADED
    assert ladder.latch_degraded("again") is None

    with pytest.raises(ValueError):
        LadderConfig(brownout_pressure=0.9, shed_pressure=0.5).validate()


# ---------------------------------------------------------------------------
# chaos knobs: parsing + determinism contract
# ---------------------------------------------------------------------------
def test_chaos_serve_knobs():
    env = {"DSTPU_CHAOS_SERVE_SLOW_TICK": "4:0.01",
           "DSTPU_CHAOS_SERVE_KV_PRESSURE": "0.8:5:9",
           "DSTPU_CHAOS_SERVE_POISON_UID": "3"}
    cfg = ChaosConfig.from_env(env)
    assert cfg.active
    assert cfg.serve_slow_tick_every == 4 and cfg.serve_slow_tick_s == 0.01
    assert cfg.serve_kv_pressure_frac == 0.8
    assert (cfg.serve_kv_pressure_from, cfg.serve_kv_pressure_until) == (5, 9)
    assert cfg.serve_poison_uid == 3
    # probability spelling parses through the sha-roll path
    pcfg = ChaosConfig.from_env({"DSTPU_CHAOS_SERVE_SLOW_TICK": "p0.25:0.5"})
    assert pcfg.serve_slow_tick_prob == 0.25 and pcfg.serve_slow_tick_every == 0

    monkey = ChaosMonkey(cfg)
    # pressure window [5, 9): off, on, off again — with edge instants
    assert monkey.serve_kv_pressure(4) == 0.0
    assert monkey.serve_kv_pressure(5) == 0.8
    assert monkey.serve_kv_pressure(8) == 0.8
    assert monkey.serve_kv_pressure(9) == 0.0
    assert monkey.injected["serve_kv_pressure"] == 1   # one ON edge

    # slow tick: every 4th, injected count exact
    stalled = [monkey.serve_slow_tick(t) for t in range(1, 9)]
    assert [s > 0 for s in stalled] == [False, False, False, True,
                                        False, False, False, True]
    assert monkey.injected["serve_slow_tick"] == 2

    # poison raises only when the uid is resident; classifies TRANSIENT
    from deepspeed_tpu.comm.guard import CommOutcome, classify_exception
    monkey.maybe_poison_serve([1, 2])     # not resident: no raise
    with pytest.raises(ChaosInjectedPoisonError) as ei:
        monkey.maybe_poison_serve([2, 3])
    assert classify_exception(ei.value) is CommOutcome.TRANSIENT

    # sha-roll determinism: same (seed, kind, tick) -> same decision
    m1 = ChaosMonkey(ChaosConfig(seed=7, serve_slow_tick_prob=0.5,
                                 serve_slow_tick_s=0.0))
    m2 = ChaosMonkey(ChaosConfig(seed=7, serve_slow_tick_prob=0.5,
                                 serve_slow_tick_s=0.0))
    rolls1 = [m1._roll("serve_slow", t) for t in range(20)]
    rolls2 = [m2._roll("serve_slow", t) for t in range(20)]
    assert rolls1 == rolls2


# ---------------------------------------------------------------------------
# serving config group (DS006-clean constants)
# ---------------------------------------------------------------------------
def test_serving_config_from_ds_config():
    cfg = ServingConfig.from_ds_config({
        "train_batch_size": 8,
        "serving": {"max_queue_depth": 4, "kv_offload_enabled": True,
                    "brownout_pressure": 0.5}})
    assert cfg.max_queue_depth == 4
    assert cfg.kv_offload_enabled and cfg.brownout_pressure == 0.5
    assert ServingConfig.from_ds_config({}).max_queue_depth == 256
    with pytest.raises(ValueError, match="unknown 'serving' config keys"):
        ServingConfig.from_ds_config({"serving": {"max_que_depth": 4}})


# ---------------------------------------------------------------------------
# engine-level KV offload: demote/promote round-trip is bit-identical
# ---------------------------------------------------------------------------
def test_kv_offload_demote_promote_parity(model_and_params):
    cfg, params = model_and_params
    prompts = [list(range(1, 20)), list(range(3, 15))]
    ref = _engine(cfg, params)
    ref.put([1, 2], prompts)
    for _ in range(9):
        ref.step()
    ref_gen = {u: list(ref.state.get(u).generated) for u in (1, 2)}

    e = _engine(cfg, params)
    e.put([1, 2], prompts)
    for _ in range(3):
        e.step()
    free_before = e.kv.free_blocks
    nbytes = e.demote_kv(1)
    assert nbytes > 0 and e.kv.free_blocks > free_before
    assert e.state.get(1).paused and e.state.get(1).blocks == []
    assert e.demoted_uids() == [1] and e.host_kv_bytes() == nbytes
    assert e.demote_kv(1) == 0            # idempotent: already demoted
    for _ in range(3):
        e.step()                          # seq 2 decodes alone
    assert e.promote_kv(1) == nbytes
    assert not e.state.get(1).paused and e.host_kv_bytes() == 0
    while any(len(e.state.get(u).generated) < len(ref_gen[u])
              for u in (1, 2)):
        e.step()
    for u in (1, 2):
        assert e.state.get(u).generated[:len(ref_gen[u])] == ref_gen[u], \
            f"uid {u} diverged after demote/promote round-trip"
    # flush clears both tiers; ledger returns to zero
    e.demote_kv(2)
    e.flush(1), e.flush(2)
    ledger = e.kv_ledger()
    assert ledger["device_blocks_reserved"] == 0
    assert ledger["host_entries"] == 0 and ledger["host_bytes"] == 0
    assert ledger["demotions"] == 2 and ledger["promotions"] == 1


# ---------------------------------------------------------------------------
# fake engines for exact-tick fault isolation / drift tests
# ---------------------------------------------------------------------------
class _FakeEngine(ResidentEngine):
    """Functional minimal engine: one token per resident sequence per
    step; scriptable step failures by 1-based step-call index. A sequence
    holds a block until it is reaped."""

    def __init__(self, fail_calls=(), fail_exc=None):
        super().__init__()
        self.step_calls = 0
        self.fail_calls = set(fail_calls)
        self.fail_exc = fail_exc or RuntimeError("connection reset by peer")

    def kv_reserved_blocks(self):
        return len(self._resident) + len(self._finished)

    def kv_blocks_needed(self, num_tokens):
        return (num_tokens + 15) // 16

    def step(self):
        self.step_calls += 1
        if self.step_calls in self.fail_calls:
            raise self.fail_exc
        return super().step()


def test_transient_step_fault_recovers_without_restart():
    """Satellite regression: a transient engine-step failure must NOT
    latch the sticky degraded 503 — the suspect is evicted, retried, and
    the server keeps answering 200s without a restart."""
    engine = _FakeEngine(fail_calls={1})
    server = InferenceServer(engine, ServingConfig(
        recover_clean_steps=3, poison_retry_budget=1, idle_poll_s=0.001))
    req = server.submit([1, 2, 3], max_new_tokens=4)
    for _ in range(20):
        _tick(server)
        if req.state.terminal:
            break
    assert req.state == RequestState.FINISHED
    assert req.fault_count == 1            # evicted once, retried, finished
    assert server._degraded is None
    assert server.ladder.level is not ServeLevel.DEGRADED
    snap = server.metrics.snapshot()
    assert snap["engine_step_faults"] == 1
    assert snap["degraded_latches"] == 0
    assert snap["recomputed_tokens"] >= 3  # the re-prefilled prompt
    # the server still takes and completes NEW work (the "200s resume")
    req2 = server.submit([4, 5], max_new_tokens=2)
    for _ in range(20):
        _tick(server)
        if req2.state.terminal:
            break
    assert req2.state == RequestState.FINISHED
    # and health auto-recovered after recover_clean_steps clean steps
    assert server.health()["fault_episode"] is False
    assert server.metrics.snapshot()["fault_recoveries"] == 1


def test_fatal_step_fault_still_latches_degraded():
    """The sticky path survives the overreach fix: fatal classifications
    (no transient marker) latch exactly as before."""
    engine = _FakeEngine(fail_calls={1, 2, 3, 4},
                         fail_exc=RuntimeError("kaboom: device went away"))
    server = InferenceServer(engine, ServingConfig(idle_poll_s=0.001))
    req = server.submit([1, 2, 3], max_new_tokens=4)
    for _ in range(5):
        _tick(server)
        if req.state.terminal:
            break
    assert req.state == RequestState.FAILED
    assert server._degraded is not None
    assert server.ladder.level is ServeLevel.DEGRADED
    assert server.metrics.snapshot()["degraded_latches"] == 1


def test_repeated_unattributed_faults_latch_degraded():
    """A step that faults every time (transient-shaped) with eviction
    never isolating it must eventually latch — the engine itself is sick.
    The latch fires through the 4x backstop (suspects keep existing, but
    the fault streak never sees a clean step)."""
    engine = _FakeEngine(fail_calls=set(range(1, 100)))
    server = InferenceServer(engine, ServingConfig(
        poison_retry_budget=0, max_consecutive_step_faults=1,
        idle_poll_s=0.001))
    reqs = [server.submit([i + 1], max_new_tokens=2) for i in range(6)]
    for _ in range(30):
        _tick(server)
        if server._degraded is not None:
            break
    assert server._degraded is not None
    assert server.ladder.level is ServeLevel.DEGRADED
    assert all(r.state == RequestState.FAILED for r in reqs)
    # isolation was attempted before giving up (quarantines precede latch)
    assert server.metrics.snapshot()["requests_quarantined"] >= 3


class _DriftEngine(_FakeEngine):
    """Fake engine whose observed KV reservation is test-controlled — the
    projected-vs-observed drift recalibration surface."""

    def __init__(self):
        super().__init__()
        self.reserved = 0

    def kv_block_bytes(self):
        return 1024

    def kv_reserved_blocks(self):
        return self.reserved


def test_kv_drift_recalibrates_projected_watermark():
    engine = _DriftEngine()
    server = InferenceServer(engine, ServingConfig(idle_poll_s=0.001))
    tracer = get_tracer()
    tracer.configure(enabled=True)
    before = tracer.instant_counts(prefix="serve/kv_recalibrate").get(
        "serve/kv_recalibrate", 0)
    # observed >> projected (0): the unsafe direction -> watermark scales
    # down (edge-triggered, once)
    engine.reserved = 10
    _tick(server)
    assert server._kv_watermark_scale == 0.5
    snap = server.metrics.snapshot()
    assert snap["kv_drift_events"] == 1
    assert snap["kv_recalibrations"] == 1
    _tick(server)                      # still drifted: NO second event
    assert server.metrics.snapshot()["kv_drift_events"] == 1
    # drift clears -> scale restored, second recalibration logged
    engine.reserved = 0
    _tick(server)
    assert server._kv_watermark_scale == 1.0
    snap = server.metrics.snapshot()
    assert snap["kv_recalibrations"] == 2
    counts = tracer.instant_counts(prefix="serve/kv_recalibrate")
    assert counts.get("serve/kv_recalibrate", 0) - before == 2


# ---------------------------------------------------------------------------
# brownout semantics: low-priority admits pause, budgets cap
# ---------------------------------------------------------------------------
def test_brownout_pauses_low_priority_and_caps_budget():
    engine = _FakeEngine()
    server = InferenceServer(engine, ServingConfig(
        brownout_max_new_tokens=3, idle_poll_s=0.001))
    low = server.submit([1, 2], max_new_tokens=5, priority=-1)
    server.ladder.observe(0.9)             # force BROWNOUT
    assert server.ladder.level is ServeLevel.BROWNOUT
    # budget capped at the door while browned out
    capped = server.submit([3, 4], max_new_tokens=50)
    assert capped.max_new_tokens == 3
    server._admit_from_queue()
    # the low-priority request waits in the queue; normal work admitted
    assert low.state == RequestState.QUEUED
    assert capped.state == RequestState.PREFILL
    # back to healthy: the low-priority admit resumes
    for _ in range(100):
        if server.ladder.observe(0.0) is not None:
            break
    assert server.ladder.level is ServeLevel.HEALTHY
    server._admit_from_queue()
    assert low.state == RequestState.PREFILL
    # stringly-typed priority is a client error at the door
    with pytest.raises(ValueError, match="priority"):
        server.submit([1], max_new_tokens=2, priority="high")


# ---------------------------------------------------------------------------
# ACCEPTANCE: chaos KV-pressure drill — brownout before the first 429,
# shed with Retry-After, recovery to healthy, episode on the trace
# ---------------------------------------------------------------------------
def test_chaos_kv_pressure_ladder_drill(model_and_params, monkeypatch):
    cfg, params = model_and_params
    monkeypatch.setenv("DSTPU_CHAOS_SERVE_KV_PRESSURE", "0.85:0:1200")
    tracer = get_tracer()
    tracer.configure(enabled=True)
    tracer.clear()
    # host budget ~20 blocks: the tier absorbs the first wave, then fills
    # — pressure must SURFACE through the ladder instead of silently
    # swallowing the whole siege into host RAM. The wide queue (32) makes
    # the FIRST 429 come from the ladder/projection, which are
    # structurally downstream of brownout
    server = InferenceServer(_engine(cfg, params), ServingConfig(
        max_queue_depth=32, kv_offload_enabled=True,
        host_kv_budget_bytes=20 * 16384,
        brownout_pressure=0.5, shed_pressure=0.9, ladder_hysteresis=0.1,
        ladder_cooldown_ticks=6, kv_demote_watermark=0.8,
        kv_demote_watermark_brownout=0.4, idle_poll_s=0.001,
        retry_after_s=0.05)).start()
    try:
        # warm the compile cache with a wave shaped exactly like the siege
        # (prefill bucket + decode batch buckets 1/2/4): a mid-siege XLA
        # compile would stall the serve tick for seconds and let the queue
        # fill before the ladder can even observe once
        warm = [server.submit(list(np.random.default_rng(100 + i)
                                   .integers(1, 99, 16)),
                              max_new_tokens=8) for i in range(4)]
        for w in warm:
            w.result(timeout=300)
        # siege: arrivals outpace the pressure-throttled service rate
        accepted, rejections = [], 0
        first_reject_eid = None
        for i in range(60):
            try:
                accepted.append(server.submit(
                    list(np.random.default_rng(i).integers(1, 99, 16)),
                    max_new_tokens=8))
            except BackpressureError as e:
                rejections += 1
                assert e.retry_after_s > 0          # Retry-After semantics
                if first_reject_eid is None:
                    evs = [ev for ev in tracer.events_snapshot()
                           if ev[1] == "serve/backpressure"]
                    first_reject_eid = evs[0][0] if evs else None
            time.sleep(0.005)
        assert rejections > 0, "pressure never pushed back"
        # everything accepted reaches a terminal state (slower, not dead)
        for r in accepted:
            r.result(timeout=300)
        assert all(r.state == RequestState.FINISHED for r in accepted)
        # ladder climbed: brownout BEFORE the first 429 (event-id order)
        snap = server.metrics.snapshot()
        assert snap["brownout_entries"] >= 1
        assert snap["shed_entries"] >= 1, snap
        assert snap["kv_demotions"] > 0
        assert snap["degraded_latches"] == 0        # sticky-503 count == 0
        brownout_evs = [ev for ev in tracer.events_snapshot()
                        if ev[1] == "serve/ladder"
                        and ev[7] and ev[7].get("to") == "brownout"]
        assert brownout_evs, "no brownout edge on the trace"
        assert first_reject_eid is not None
        assert brownout_evs[0][0] < first_reject_eid, \
            "server rejected before visiting brownout"
        # pressure lifts at tick 1200: the ladder climbs back down
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if server.ladder.level is ServeLevel.HEALTHY:
                break
            time.sleep(0.05)
        assert server.ladder.level is ServeLevel.HEALTHY
        assert server.health()["status"] == "serving"
        # the whole episode is reconstructible from the trace; the chaos
        # OFF edge lands when the (still-ticking idle) loop passes the
        # window end, so poll for it bounded
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if tracer.instant_counts().get("chaos/serve_kv_pressure",
                                           0) >= 2:
                break
            time.sleep(0.1)
        counts = tracer.instant_counts()
        assert counts.get("chaos/serve_kv_pressure", 0) >= 2   # on + off
        assert counts.get("serve/kv_demote", 0) == snap["kv_demotions"]
        assert counts.get("serve/ladder", 0) == snap["ladder_transitions"]
        # and the KV ledger is clean (both tiers)
        ledger = server.engine.kv_ledger()
        assert ledger["device_blocks_reserved"] == 0
        assert ledger["host_entries"] == 0 and ledger["host_bytes"] == 0
    finally:
        server.stop(drain_timeout=30.0)


# ---------------------------------------------------------------------------
# ACCEPTANCE: poison-request drill — quarantined after its retry budget
# while concurrent well-formed requests complete and health recovers
# ---------------------------------------------------------------------------
def test_poison_request_quarantine_drill(model_and_params):
    cfg, params = model_and_params
    chaos = ChaosMonkey(ChaosConfig(serve_poison_uid=2))
    tracer = get_tracer()
    tracer.configure(enabled=True)
    server = InferenceServer(_engine(cfg, params), ServingConfig(
        poison_retry_budget=1, recover_clean_steps=3,
        max_consecutive_step_faults=8, idle_poll_s=0.001),
        chaos=chaos).start()
    try:
        good_a = server.submit([5, 5, 5, 5], max_new_tokens=6)
        poison = server.submit([6, 6, 6, 6], max_new_tokens=6)   # uid 2
        good_b = server.submit([7, 7, 7, 7], max_new_tokens=6)
        assert poison.uid == 2
        for r in (good_a, poison, good_b):
            r.wait(timeout=300)
        # the poison is quarantined after its retry budget...
        assert poison.state == RequestState.FAILED
        assert poison.finish_reason == "quarantined"
        assert poison.fault_count == 2       # initial + 1 retry
        # ...while concurrent well-formed requests complete normally
        assert good_a.state == RequestState.FINISHED
        assert good_b.state == RequestState.FINISHED
        assert len(good_a.tokens) == 6 and len(good_b.tokens) == 6
        snap = server.metrics.snapshot()
        assert snap["requests_quarantined"] == 1
        assert snap["degraded_latches"] == 0
        assert snap["engine_step_faults"] >= 2
        assert chaos.injected["serve_poison"] >= 2
        # health returns to ok after the clean-step window
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            h = server.health()
            if h["ok"] and not h["fault_episode"]:
                break
            # keep clean steps flowing
            server.submit([8, 8], max_new_tokens=2).wait(timeout=60)
        h = server.health()
        assert h["ok"] and h["fault_episode"] is False
        assert server.metrics.snapshot()["fault_recoveries"] >= 1
        assert tracer.instant_counts().get("serve/quarantine", 0) >= 1
    finally:
        server.stop(drain_timeout=30.0)


# ---------------------------------------------------------------------------
# graceful drain under load: every request terminal, streams closed,
# the KV ledger returns to zero in BOTH tiers
# ---------------------------------------------------------------------------
def test_graceful_drain_under_load_ledger_zero(model_and_params):
    cfg, params = model_and_params
    server = InferenceServer(_engine(cfg, params, kv_blocks=16),
                             ServingConfig(
        kv_offload_enabled=True, kv_demote_watermark=0.35,
        kv_demote_watermark_brownout=0.25, idle_poll_s=0.001)).start()
    try:
        rng = np.random.default_rng(3)
        reqs = [server.submit(list(rng.integers(1, 99, 16)),
                              max_new_tokens=6) for _ in range(8)]
        # drain mid-decode: wait until tokens are actually flowing
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if any(r.tokens for r in reqs):
                break
            time.sleep(0.005)
        assert server.drain(timeout=300), "drain timed out under load"
        # every request reached a terminal state with its full budget
        assert all(r.state == RequestState.FINISHED for r in reqs)
        assert all(len(r.tokens) == 6 for r in reqs)
        # streams are closed: iterating an unconsumed stream yields the
        # full token list then terminates (END sentinel) instead of
        # blocking on a next token that will never come
        for r in reqs:
            assert list(r.stream(timeout=1.0)) == r.tokens
        # the tier actually exercised during the run...
        assert server.metrics.snapshot()["kv_demotions"] > 0
        # ...and the ledger is zero in both tiers
        ledger = server.engine.kv_ledger()
        assert ledger["device_blocks_reserved"] == 0
        assert ledger["host_entries"] == 0 and ledger["host_bytes"] == 0
        assert server.engine.kv_occupancy() == 0.0
    finally:
        server.stop(drain_timeout=10.0)


# ---------------------------------------------------------------------------
# bench_serve micro scenario (the tier-1 serve_load gate): deterministic
# counter invariants on a ~100-request closed-loop run
# ---------------------------------------------------------------------------
def test_bench_serve_micro_counter_invariants(model_and_params):
    import dataclasses as dc

    from deepspeed_tpu.serving.bench_serve import SCENARIOS, run_scenario

    cfg, params = model_and_params
    scenario = dc.replace(SCENARIOS["micro"], num_requests=100,
                          prompt_len=(8, 24), max_new_tokens=(2, 5))
    # scope the span-derived latency section to THIS run's request uids
    get_tracer().configure(enabled=True)
    get_tracer().clear()
    server = InferenceServer(_engine(cfg, params, kv_blocks=16),
                             ServingConfig(
        max_queue_depth=32, kv_offload_enabled=True,
        kv_demote_watermark=0.35, kv_demote_watermark_brownout=0.25,
        brownout_pressure=0.6, shed_pressure=0.95,
        ladder_cooldown_ticks=5, idle_poll_s=0.001,
        retry_after_s=0.01)).start()
    try:
        report = run_scenario(server, scenario)
    finally:
        server.stop(drain_timeout=30.0)
    m = report["metrics"]
    c = report["counters"]
    # conservation: every submitted request reached exactly one terminal
    assert m["requests_submitted"] == 100
    assert (m["requests_completed"] + m["requests_failed"]
            + m["requests_cancelled"] + m["requests_timed_out"]) == 100
    assert m["requests_failed"] == 0
    assert report["requests"]["states"] == {"finished": 100}
    # token conservation: engine-side count == client-side count
    assert m["tokens_generated"] == report["requests"]["client_tokens"]
    assert m["tokens_generated"] >= 2 * 100
    # the tier was exercised AND balanced back to zero
    assert c["demotions"] > 0
    assert c["demotions"] == c["promotions"]
    assert c["demoted_bytes"] == c["promoted_bytes"]
    assert report["kv_ledger"]["device_blocks_reserved"] == 0
    assert report["kv_ledger"]["host_entries"] == 0
    assert report["kv_ledger"]["host_bytes"] == 0
    # availability: the siege never latched the sticky 503
    assert c["sticky_503"] == 0
    assert c["quarantined"] == 0 and c["step_faults"] == 0
    assert report["drained"] is True
    assert report["ladder"]["level"] == "healthy"
    # span-derived latencies cover the full population
    ttft = report["latency_from_trace"]["ttft_s"]
    assert ttft["count"] == 100 and ttft["p50_s"] > 0
    tpot = report["latency_from_trace"]["tpot_s"]
    assert tpot["count"] > 0 and tpot["p50_s"] > 0
    # and the report is JSON-serializable (the CLI contract)
    import json
    json.dumps(report, default=str)
