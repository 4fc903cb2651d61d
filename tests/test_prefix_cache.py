"""Radix prefix cache over KV pages + quantized host-tier KV.

Unit pieces (trie, planners, page codec, the pinned-scale release fix)
run without a model; engine-level tests share the tiny fp32 llama and
the KV/bucket shapes of tests/test_serving.py (one compile per shape per
process); the bench_serve multi_turn drill is the tier-1 acceptance gate
for the counter-conservation identity
``prefill_tokens_saved + prefill_tokens_computed == prefill_tokens_total``.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache, KVCacheConfig
from deepspeed_tpu.inference.v2.kv_offload import (dequantize_pages,
                                                   quantize_error_bound,
                                                   quantize_pages)
from deepspeed_tpu.inference.v2.prefix_cache import PrefixCache
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import (TINY_LLAMA, LlamaConfig,
                                        LlamaForCausalLM)
from deepspeed_tpu.serving.kv_tier import plan_prefix_evictions

pytestmark = pytest.mark.prefix


@pytest.fixture(scope="module")
def model_and_params():
    cfg = LlamaConfig(**{**TINY_LLAMA.__dict__, "dtype": jnp.float32,
                         "max_seq_len": 512})
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return cfg, params


def _engine(cfg, params, prefix=True, kv_blocks=64, **kw):
    return InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=kv_blocks,
        scheduler=SchedulerConfig(max_tokens_per_step=64,
                                  prefill_buckets=(16, 32, 64)),
        prefix_cache_enabled=prefix, **kw))


# ---------------------------------------------------------------------------
# trie unit (pure bookkeeping — no model, no device)
# ---------------------------------------------------------------------------
def test_trie_lookup_pins_and_full_block_cap():
    c = PrefixCache(block_size=4)
    toks = list(range(100, 112))                       # 3 full blocks
    # nothing cached -> miss
    blocks, matched = c.admit_match(1, toks)
    assert blocks == [] and matched == 0
    assert c.stats.misses == 1
    # register 3 full blocks for uid 1 (pinned)
    assert c.insert_from_seq(1, toks, [5, 6, 7], seen_tokens=12) == 3
    assert c.cached_blocks() == 3 and c.pinned_blocks() == 3
    assert c.evictable_blocks() == 0
    # exact-length lookup caps at (len-1)//bs = 2 blocks: the last token
    # must always be computed to produce first-sample logits
    blocks, matched = c.admit_match(2, toks)
    assert blocks == [5, 6] and matched == 8
    # longer prompt with the same prefix matches all 3 blocks
    blocks, matched = c.admit_match(3, toks + [1, 2, 3, 4, 5])
    assert blocks == [5, 6, 7] and matched == 12
    assert sorted(c.pinned_block_ids()) == [5, 6, 7]
    # drop every reader: blocks STAY cached, now evictable
    for uid in (1, 2, 3):
        c.release_seq(uid)
    assert c.cached_blocks() == 3 and c.evictable_blocks() == 3
    snap = c.snapshot()
    assert snap["hit_tokens"] == 8 + 12
    assert snap["hits"] == 2 and snap["misses"] == 1


def test_trie_eviction_is_lru_leaf_first():
    c = PrefixCache(block_size=2)
    c.insert_from_seq(1, [1, 2, 3, 4, 5, 6], [10, 11, 12], 6)  # chain 10-11-12
    c.insert_from_seq(2, [1, 2, 9, 9], [10, 20], 4)            # branch 20
    c.release_seq(1)
    c.release_seq(2)
    # leaf-first: the root block 10 (shared by both chains) cannot go
    # before its children; oldest-stamp leaf goes first
    plan = c.plan_evictions(2)
    assert 10 not in plan and len(plan) == 2
    freed = c.evict_blocks(plan)
    assert freed == plan
    # the remaining chain evicts completely, deepest first
    rest = c.plan_evictions(10)
    assert rest[-1] == 10                  # root only after its subtree
    c.evict_blocks(rest)
    assert c.cached_blocks() == 0
    assert c.stats.evicted_blocks == 4
    # pinned nodes never evict
    c.insert_from_seq(3, [1, 2], [30], 2)
    assert c.plan_evictions(5) == []


def test_trie_soft_cap_and_planner():
    c = PrefixCache(block_size=2, max_cached_blocks=1)
    c.insert_from_seq(1, [1, 2, 3, 4], [10, 11], 4, pin=False)
    assert c.over_cap_blocks() == 1
    # planner: over-cap trim even without pressure
    assert plan_prefix_evictions(2, c.over_cap_blocks(),
                                 reserved_blocks=0,
                                 demote_line_blocks=100.0) == 1
    # pressure: evict down to the demote line, bounded by evictable
    assert plan_prefix_evictions(5, 0, reserved_blocks=12,
                                 demote_line_blocks=8.0) == 4
    assert plan_prefix_evictions(2, 0, reserved_blocks=12,
                                 demote_line_blocks=8.0) == 2
    assert plan_prefix_evictions(0, 0, 12, 8.0) == 0
    assert plan_prefix_evictions(5, 0, 4, 8.0) == 0


# ---------------------------------------------------------------------------
# the pinned-scale release fix (fp8 pages shared by refcount)
# ---------------------------------------------------------------------------
def test_release_skips_pages_pinned_by_prefix_cache():
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=1, num_kv_heads=2, head_dim=4, block_size=4,
        num_blocks=8, dtype=jnp.float8_e4m3fn))
    blocks = kv.reserve(3)
    # grow the shared page's scale (as an outlier write would)
    kv.scales = kv.scales.at[:, :, :, blocks[0]].set(2.5)
    kv.scales = kv.scales.at[:, :, :, blocks[1]].set(3.5)
    free_before = kv.free_blocks
    # one reader releases its whole block list; page blocks[0] is still
    # pinned by the prefix cache (refcount > 0 — another reader)
    kv.release(blocks[:2], pinned=[blocks[0]])
    # the pinned page: NOT freed, scale NOT clobbered
    assert kv.free_blocks == free_before + 1
    assert float(kv.scales[0, 0, 0, blocks[0]]) == 2.5
    # the unpinned page was freed and its scale reset
    assert float(kv.scales[0, 0, 0, blocks[1]]) == 1.0
    # plain release (no pins) keeps the old semantics
    kv.release([blocks[2]])
    assert kv.free_blocks == free_before + 2


# ---------------------------------------------------------------------------
# host-tier page codec
# ---------------------------------------------------------------------------
def test_page_codec_round_trips_within_bound():
    rng = np.random.default_rng(0)
    data = (rng.normal(size=(2, 2, 2, 4, 8, 4)) * 3).astype(np.float32)
    data[0, 0, 0, 1] = 0.0                       # an all-zero page
    for codec, ratio in (("int8", 4), ("fp8", 4)):
        stored, qs = quantize_pages(data, codec)
        assert data.nbytes // stored.nbytes == ratio
        deq = dequantize_pages(stored, qs, codec, np.float32)
        bound = quantize_error_bound(qs, codec)
        assert bound > 0.0
        assert float(np.max(np.abs(deq - data))) <= bound
        # the all-zero page survives exactly (scale clamped to 1.0)
        assert np.all(deq[0, 0, 0, 1] == 0.0)
    # "none" is the identity in both directions
    stored, qs = quantize_pages(data, "none")
    assert stored is data and qs is None
    assert dequantize_pages(stored, qs, "none", np.float32) is data
    with pytest.raises(ValueError):
        quantize_pages(data, "int4")


def test_quantized_demote_promote_tolerance(model_and_params):
    cfg, params = model_and_params
    eng = _engine(cfg, params)
    rng = np.random.default_rng(1)
    prompt = [int(t) for t in rng.integers(1, 99, 40)]
    eng.put([1], [prompt])
    eng.put([2], [prompt[:20] + [7, 8, 9, 11, 12]])   # keeps prefix pinned
    seq = eng.state.get(1)
    before = np.asarray(eng.kv.data[:, :, :, np.asarray(seq.blocks)])
    eng.demote_kv(1, quantize="int8")
    entry = eng.host_kv.get(1)
    assert entry.codec == "int8"
    # the compression headline: stored bytes ~4x under raw (scale arrays
    # cost a little)
    assert entry.raw_nbytes / entry.nbytes > 3.5
    assert eng.host_kv.compression_ratio() > 3.5
    assert eng.promote_kv(1) is not None
    seq = eng.state.get(1)
    after = np.asarray(eng.kv.data[:, :, :, np.asarray(seq.blocks)])
    # the contract is the BOUND (a round-trip may even be exact)
    err = float(np.max(np.abs(after - before)))
    assert err <= quantize_error_bound(entry.qscales, "int8")
    # full-width demotion round-trips bit-identical
    eng.demote_kv(1, quantize="none")
    assert eng.host_kv.get(1).codec == "none"
    eng.promote_kv(1)
    seq = eng.state.get(1)
    again = np.asarray(eng.kv.data[:, :, :, np.asarray(seq.blocks)])
    assert bool((again == after).all())
    # both tiers drain to zero
    eng.flush(1)
    eng.flush(2)
    ledger = eng.kv_ledger()
    assert ledger["host_entries"] == 0 and ledger["host_bytes"] == 0
    assert ledger["device_blocks_reserved"] == 0


# ---------------------------------------------------------------------------
# engine composition: cache hits, conservation, speculative decoding
# ---------------------------------------------------------------------------
def test_prefix_hit_identical_tokens_and_conservation(model_and_params):
    cfg, params = model_and_params
    rng = np.random.default_rng(2)
    prompt = [int(t) for t in rng.integers(1, 99, 40)]
    warm = _engine(cfg, params)
    out1 = warm.generate(prompt, max_new_tokens=6, uid=1)
    out2 = warm.generate(prompt, max_new_tokens=6, uid=2)   # cache hit
    cold = _engine(cfg, params, prefix=False)
    ref = cold.generate(prompt, max_new_tokens=6, uid=1)
    assert out1 == ref and out2 == ref
    st = warm.prefix_stats()
    # 40-token prompt, 16-token blocks -> 2 full blocks reused
    assert st["prefill_tokens_saved"] == 32
    assert st["prefill_tokens_saved"] + st["prefill_tokens_computed"] == \
        st["prefill_tokens_total"]
    assert st["prefix_hit_ratio"] > 0.0
    # flush-time absorption kept the blocks cached, unpinned
    assert st["prefix_cached_blocks"] > 0
    assert st["prefix_pinned_blocks"] == 0


def test_speculative_decoding_composes_with_prefix_hits(model_and_params):
    """bench_decode's speculative_gate contract at tier-1 scale: a
    prefix-cache-hit prompt must produce IDENTICAL tokens to a
    cold-prefill run under speculative decoding (cache hits must not
    desync the draft/verify engines)."""
    cfg, params = model_and_params
    rng = np.random.default_rng(3)
    base = [int(t) for t in rng.integers(1, 99, 24)]
    # repeated n-grams in the prompt + the tiny model's looping argmax
    # chain give prompt-lookup real proposals within 24 decode tokens
    prompt = base + base
    warm = _engine(cfg, params, speculative_k=4)
    out1 = warm.generate(prompt, max_new_tokens=24, uid=1)
    out2 = warm.generate(prompt, max_new_tokens=24, uid=2)   # cache hit
    cold = _engine(cfg, params, prefix=False, speculative_k=4)
    ref = cold.generate(prompt, max_new_tokens=24, uid=1)
    assert out1 == ref and out2 == ref
    assert warm.prefix_stats()["prefill_tokens_saved"] > 0
    # speculation actually ran (the composition is exercised, not idle)
    assert warm.speculative_stats()["steps"] > 0


def test_eviction_order_shared_prefix_outlives_unshared(model_and_params):
    """The demotion-ordering acceptance drill: under pressure, unpinned
    cached pages evict first, unshared live pages demote to the host
    tier, and the pinned shared prefix outlives them all on device —
    when its last reader demotes, it survives via the host entry (never
    discarded)."""
    cfg, params = model_and_params
    eng = _engine(cfg, params)
    rng = np.random.default_rng(4)
    shared = [int(t) for t in rng.integers(1, 99, 40)]
    # A materializes the prefix; B shares it (pins refs to 2)
    eng.put([1], [shared])
    eng.put([2], [shared + [5, 6, 7]])
    shared_blocks = set(eng.state.get(1).blocks[:2])
    assert shared_blocks == set(eng.state.get(2).blocks[:2])
    # C is unshared traffic that finishes: its pages become unpinned cache
    eng.put([3], [[int(t) for t in rng.integers(1, 99, 36)]])
    eng.finish(3)
    unshared_cached = set(eng.state.get(3).blocks)
    eng.reap_finished()
    cache = eng.prefix_cache
    assert cache.evictable_blocks() > 0
    # pressure step 1: cache eviction — only unpinned pages go
    freed = eng.evict_prefix_blocks(100)
    assert freed == cache.stats.evicted_blocks and freed > 0
    assert all(not cache.owns(b) or b in shared_blocks
               for b in unshared_cached)
    assert all(cache.owns(b) for b in shared_blocks)   # prefix survives
    # pressure step 2: demote the unshared reader A — shared pages stay
    # on device (B still reads them), A's entry carries a copy
    eng.demote_kv(1, quantize="int8")
    assert all(cache.owns(b) for b in shared_blocks)
    assert sorted(cache.pinned_block_ids()) == sorted(shared_blocks)
    # B keeps decoding against the shared pages while A is away
    assert 2 in {s.uid for s in eng.state.decoding()}
    out = eng.step()
    assert 2 in out
    # pressure step 3: the LAST reader demotes — the prefix is still not
    # discarded: it stays cached (evictable) AND rides B's host entry
    eng.demote_kv(2, quantize="int8")
    assert all(cache.owns(b) for b in shared_blocks)
    assert cache.pinned_blocks() == 0
    assert eng.host_kv.get(2).codec == "int8"
    # promotion restores both; decode resumes
    assert eng.promote_kv(1) is not None
    assert eng.promote_kv(2) is not None
    out = eng.step()
    assert 1 in out and 2 in out
    ledger = eng.kv_ledger()
    assert ledger["host_entries"] == 0


# ---------------------------------------------------------------------------
# serving config + metrics surface
# ---------------------------------------------------------------------------
def test_serving_config_prefix_keys():
    from deepspeed_tpu.serving import ServingConfig
    cfg = ServingConfig.from_ds_config({"serving": {
        "prefix_cache_enabled": True, "host_kv_quantize": "int8",
        "prefix_cache_max_blocks": 8}})
    assert cfg.prefix_cache_enabled and cfg.host_kv_quantize == "int8"
    assert cfg.prefix_cache_max_blocks == 8

    from deepspeed_tpu.serving import InferenceServer
    from serving_fakes import ResidentEngine
    with pytest.raises(ValueError, match="host_kv_quantize"):
        InferenceServer(ResidentEngine(),
                        ServingConfig(host_kv_quantize="int4"))


def test_prometheus_prefix_rows_one_type_block_each():
    from deepspeed_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    m.set_prefix_gauges({"prefill_tokens_total": 10,
                         "prefill_tokens_saved": 4,
                         "prefill_tokens_computed": 6,
                         "prefix_hits": 1, "prefix_misses": 2,
                         "prefix_hit_ratio": 0.4,
                         "prefix_cached_blocks": 3,
                         "prefix_pinned_blocks": 1},
                        resident_tokens=5, resident_bytes=50,
                        host_compression=2.0)
    m.on_prefix_evict(2)
    text = m.prometheus_text()
    for family, kind in (
            ("dstpu_serving_prefix_hits", "counter"),
            ("dstpu_serving_prefill_tokens_saved", "counter"),
            ("dstpu_serving_prefix_evictions", "counter"),
            ("dstpu_serving_prefix_cache_hit_ratio", "gauge"),
            ("dstpu_serving_host_kv_compression_ratio", "gauge"),
            ("dstpu_serving_bytes_per_resident_token", "gauge")):
        # exactly ONE TYPE metadata line per family (a duplicate fails
        # the whole Prometheus scrape — PR 8's lesson)
        assert text.count(f"# TYPE {family} {kind}\n") == 1, family
    snap = m.snapshot()
    assert snap["bytes_per_resident_token"] == 10.0
    assert snap["host_kv_compression_ratio"] == 2.0
    # the serve-tick stage-share gauges ride the SAME single
    # dstpu_trace_counter TYPE block as every other counter family (a
    # second metadata block would fail the whole scrape)
    from deepspeed_tpu.telemetry import get_tracer
    tracer = get_tracer()
    was_enabled = tracer.enabled
    tracer.configure(enabled=True)
    try:
        tracer.counter("serve/tick_stage_share", cat="serve",
                       admission=0.01, prefill=0.4, decode=0.3,
                       demote=0.05, promote=0.02, drain=0.02,
                       residual=0.2)
        tracer.counter("serve/kv_bytes", cat="mem",
                       projected=1024, observed=512)
        text = m.prometheus_text()
        assert text.count("# TYPE dstpu_trace_counter gauge\n") == 1
        assert 'counter="serve/tick_stage_share",series="decode"' in text
        assert 'stat="p99"' in text        # counter tracks report tails
    finally:
        tracer.configure(enabled=was_enabled)
        tracer.clear()


def test_env_report_serving_rows(tmp_path, monkeypatch):
    import json

    from deepspeed_tpu.env_report import serving_report
    art = tmp_path / "bench_serve.json"
    art.write_text(json.dumps({
        "scenario": {"name": "multi_turn"},
        "prefix": {"prefix_hit_ratio": 0.82,
                   "prefill_tokens_saved": 3280,
                   "prefill_tokens_total": 3997,
                   "host_compression_ratio": 3.9}}))
    monkeypatch.setenv("DSTPU_SERVE_REPORT", str(art))
    rows = dict(serving_report())
    assert "82" in rows["prefix cache"]
    assert "3.9" in rows["host kv tier"]
    monkeypatch.setenv("DSTPU_SERVE_REPORT", str(tmp_path / "nope.json"))
    rows = dict(serving_report())
    assert "no artifact" in rows["prefix cache"]


def test_warm_idle_cache_is_capacity_not_pressure(model_and_params):
    """An idle server with a warm absorbed-history cache must stay
    HEALTHY: evictable cached blocks are reclaimable capacity, so they
    count neither as ladder pressure (no brownout on an idle replica)
    nor as observed sequence occupancy (no spurious kv_drift
    recalibration of the admission watermark)."""
    import time

    from deepspeed_tpu.serving import InferenceServer, ServeLevel, \
        ServingConfig

    cfg, params = model_and_params
    eng = _engine(cfg, params, kv_blocks=16)
    server = InferenceServer(eng, ServingConfig(
        kv_offload_enabled=True, prefix_cache_enabled=True,
        # thresholds a warm cache WOULD trip if miscounted as pressure
        brownout_pressure=0.3, shed_pressure=0.95, ladder_hysteresis=0.05,
        ladder_cooldown_ticks=2, kv_demote_watermark=0.9,
        idle_poll_s=0.001)).start()
    try:
        rng = np.random.default_rng(7)
        reqs = [server.submit(list(rng.integers(1, 99, 40)),
                              max_new_tokens=3) for _ in range(3)]
        for r in reqs:
            r.result(timeout=120)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and \
                server.health()["inflight"] > 0:
            time.sleep(0.005)
        # flushed sequences were absorbed: the device pool is mostly
        # cache-held, and ALL of it is evictable (no live pins)
        cache = eng.prefix_cache
        assert cache.evictable_blocks() > 0
        frac = eng.kv_reserved_blocks() / eng.kv_usable_blocks()
        assert frac > 0.3        # unfixed, this WOULD read as brownout
        time.sleep(0.1)          # plenty of idle ticks past the cooldown
        # live traffic may legitimately brown out mid-run (pinned pages
        # ARE pressure while readers hold them); the contract here is
        # the idle steady state: the warm cache alone never holds the
        # ladder up...
        assert server.ladder.level is ServeLevel.HEALTHY
        # ...and never recalibrates admission as if it were leaked blocks
        assert server._kv_watermark_scale == 1.0
        assert server.metrics.snapshot()["kv_recalibrations"] == 0
    finally:
        server.stop(drain_timeout=10.0)


# ---------------------------------------------------------------------------
# the tier-1 acceptance gate: bench_serve multi_turn prefix proof
# ---------------------------------------------------------------------------
def test_bench_serve_multi_turn_prefix_proof(model_and_params):
    from deepspeed_tpu.serving import InferenceServer, ServingConfig
    from deepspeed_tpu.serving.bench_serve import SCENARIOS, run_scenario
    from deepspeed_tpu.telemetry.tracer import get_tracer

    cfg, params = model_and_params
    scenario = dc.replace(SCENARIOS["multi_turn"], num_requests=12,
                          concurrency=3)
    get_tracer().configure(enabled=True)
    get_tracer().clear()
    server = InferenceServer(_engine(cfg, params), ServingConfig(
        max_queue_depth=32, kv_offload_enabled=True,
        prefix_cache_enabled=True, host_kv_quantize="int8",
        kv_demote_watermark=0.5, kv_demote_watermark_brownout=0.3,
        idle_poll_s=0.001, retry_after_s=0.01)).start()
    try:
        report = run_scenario(server, scenario)
    finally:
        server.stop(drain_timeout=30.0)
    assert report["requests"]["states"] == {"finished": 48}
    p = report["prefix"]
    # the headline: the cache actually killed redundant prefill
    assert p["prefix_hit_ratio"] > 0.0
    assert p["prefill_tokens_saved"] > 0
    # counter conservation, exactly
    assert p["conservation_ok"] is True
    assert p["prefill_tokens_saved"] + p["prefill_tokens_computed"] == \
        p["prefill_tokens_total"]
    # the cache can never save more than the workload made shareable
    assert p["prefill_tokens_saved"] <= p["expected_reusable_tokens"]
    # proof-set counters mirror engine truth
    c = report["counters"]
    assert c["prefill_tokens_saved"] == p["prefill_tokens_saved"]
    # availability untouched by the cache machinery
    assert c["sticky_503"] == 0 and c["quarantined"] == 0
    # the drained ledger: no sequence holds blocks in either tier (a
    # warm cache legitimately remains)
    ledger = report["kv_ledger"]
    assert ledger["device_blocks_reserved"] == 0
    assert ledger["host_entries"] == 0 and ledger["host_bytes"] == 0
    # any demotion that happened was stored quantized
    if c["demotions"]:
        assert ledger["host_compression_ratio"] > 1.0


def test_shared_prefix_shape_is_deterministic():
    from deepspeed_tpu.serving.bench_serve import SCENARIOS, _request_shape
    sc = SCENARIOS["burst"]
    assert sc.shared_prefix_frac > 0.0
    a = _request_shape(sc, 7)
    b = _request_shape(sc, 7)
    assert a == b                           # pure function of (seed, index)
    p1, _, _, s1 = _request_shape(sc, 1)
    p2, _, _, s2 = _request_shape(sc, 2)
    assert s1 > 0 and s2 > 0
    # the shared run really is shared across indices
    assert p1[:min(s1, s2)] == p2[:min(s1, s2)]
