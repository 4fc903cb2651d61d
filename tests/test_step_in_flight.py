"""One step in flight: ``engine.step`` dispatches tick k and collects tick
k-1 while a serving loop runs (``depth`` 1), and hands every other caller
this tick's tokens (``depth`` 0). Same programs, same code, another order of
the host's work: every delivered token is the one depth 0 delivers.

The server is ticked by hand here (``_serve_once`` from the test's thread,
the engine's depth set as ``_serve_loop`` sets it), so that which tick does
what is the test's to say; the threaded loop has its own cases at the end.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.sampling import SamplingConfig
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import (TINY_LLAMA, LlamaConfig,
                                        LlamaForCausalLM)
from deepspeed_tpu.runtime.precision import cast_to_compute
from deepspeed_tpu.serving.request import RequestState
from deepspeed_tpu.serving.server import InferenceServer, ServingConfig
from deepspeed_tpu.telemetry.tracer import get_tracer

BLOCK = 8
#: prompts that end their prefill at different ticks (a step takes 32 tokens)
#: and budgets that end at different ticks, one of them with the first token;
#: contexts cross several blocks of 8
PROMPTS = (5, 19, 33, 12, 27)
BUDGETS = (3, 9, 17, 1, 12)


def _dense():
    cfg = LlamaConfig(**{**TINY_LLAMA.__dict__, "dtype": jnp.float32,
                         "max_seq_len": 512})
    params = LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0),
        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return cfg, params


def _latent():
    from deepspeed_tpu.models import joyai_llm_flash as jm
    hf = {"model_type": "joyai_llm_flash", "vocab_size": 512,
          "hidden_size": 64, "intermediate_size": 128,
          "moe_intermediate_size": 32, "num_hidden_layers": 3,
          "num_attention_heads": 2, "q_lora_rank": 24, "kv_lora_rank": 16,
          "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
          "n_routed_experts": 16, "n_shared_experts": 1,
          "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
          "topk_method": "noaux_tc", "scoring_func": "sigmoid",
          "routed_scaling_factor": 2.5, "norm_topk_prob": True,
          "first_k_dense_replace": 1, "max_position_embeddings": 512,
          "rope_theta": 10000.0, "rope_interleave": True,
          "rope_scaling": None, "rms_norm_eps": 1e-6}
    cfg = dataclasses.replace(jm.joyai_flash_config_from_hf(hf),
                              dtype=jnp.float32)
    params = cast_to_compute(jm.JoyAIFlashForCausalLM(cfg).init(
        jax.random.PRNGKey(0),
        {"input_ids": np.zeros((1, 8), np.int32)})["params"], jnp.float32)
    return cfg, params


def _two_kinds():
    from benchmarks.families import laguna as family
    from deepspeed_tpu.models import laguna as lm
    hf = {"model_type": "laguna", "vocab_size": 512, "hidden_size": 64,
          "intermediate_size": 128, "num_hidden_layers": 3,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "max_position_embeddings": 512, "attention_bias": False,
          "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
          "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
          "norm_topk_prob": True, "decoder_sparse_step": 1,
          "mlp_only_layers": [0], "tie_word_embeddings": False,
          "gating": "per-head", "sliding_window": 24,
          "rope_parameters": {
              "full_attention": {"rope_type": "default", "rope_theta": 10000,
                                 "partial_rotary_factor": 1},
              "sliding_attention": {"rope_type": "default",
                                    "rope_theta": 10000,
                                    "partial_rotary_factor": 1}},
          "layer_types": ["full_attention", "sliding_attention",
                          "sliding_attention"],
          "mlp_layer_types": ["dense", "sparse", "sparse"],
          "gating_types": ["per_head"] * 3,
          "num_attention_heads_per_layer": [4, 6, 6],
          "moe_routed_scaling_factor": 2.5,
          "moe_router_logit_softcapping": 0}
    cfg, _ = family.build(hf, "serve", {"max_context": 512})
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = cast_to_compute(lm.LagunaForCausalLM(cfg).init(
        jax.random.PRNGKey(0),
        {"input_ids": np.zeros((1, 8), np.int32)})["params"], jnp.float32)
    return cfg, params


BUILDERS = {"dense": _dense, "latent": _latent, "two_page_kinds": _two_kinds}


@pytest.fixture(scope="module")
def built():
    made = {}

    def get(policy):
        if policy not in made:
            made[policy] = BUILDERS[policy]()
        return made[policy]
    return get


def _engine(cfg, params, **over):
    return InferenceEngineV2(params, cfg, V2EngineConfig(**{
        "kv_block_size": BLOCK, "kv_num_blocks": 64,
        "ctx_block_buckets": (4, 8, 16), "decode_batch_buckets": (1, 2, 4, 8),
        "scheduler": SchedulerConfig(max_tokens_per_step=32,
                                     max_decode_batch=8,
                                     prefill_buckets=(8, 16, 32)), **over}))


def _prompts(sizes=PROMPTS, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, n).tolist() for n in sizes]


class Ticked:
    """A server ticked by hand at the engine depth its loop would set."""

    def __init__(self, engine, depth=1, **serving):
        self.server = InferenceServer(engine, ServingConfig(**serving))
        self.engine = engine
        engine.depth = depth

    def tick(self, n=1):
        for _ in range(n):
            try:
                self.server._serve_once()
            except Exception as e:          # what ``_serve_ticks`` does
                from deepspeed_tpu.serving.server import _EngineStepError
                if not isinstance(e, _EngineStepError):
                    raise
                self.server._on_step_fault(e)

    def run(self, reqs, most=400):
        for _ in range(most):
            if all(r.state.terminal for r in reqs) \
                    and not self.engine.has_work():
                break
            self.tick()
        else:
            raise AssertionError("requests did not settle")
        self.tick()      # one more: a reap that waited for a dropped row
        return [list(r.tokens) for r in reqs]

    def close(self):
        self.server._collect_pending()
        self.engine.depth = 0


def _served(engine, prompts, budgets, depth=1):
    t = Ticked(engine, depth)
    reqs = [t.server.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    got = t.run(reqs)
    t.close()
    assert all(r.state is RequestState.FINISHED for r in reqs)
    return got, t


def _balanced(engine):
    ledger = engine.kv_ledger()
    assert ledger["device_blocks_reserved"] == 0, ledger
    assert not engine._pending and len(engine.state) == 0
    if engine.kv.two_kinds:
        assert engine.kv.window_allocator.free_blocks == \
            engine.kv.window_allocator.total_blocks


# --- (a) token for token ------------------------------------------------------

@pytest.mark.parametrize("policy", list(BUILDERS))
def test_a_served_run_at_depth_1_delivers_what_put_and_step_deliver(
        built, policy):
    cfg, params = built(policy)
    prompts = _prompts()
    alone = _engine(cfg, params)
    want = [alone.generate(p, max_new_tokens=n, uid=i)
            for i, (p, n) in enumerate(zip(prompts, BUDGETS))]
    got, t = _served(_engine(cfg, params), prompts, BUDGETS)
    assert got == want
    assert [len(g) for g in got] == list(BUDGETS)
    m = t.server.metrics.snapshot()
    assert m["rows_dropped"] == 0
    assert m["ticks_dispatched_ahead"] >= max(BUDGETS) - 2
    _balanced(t.engine)


@pytest.mark.parametrize("policy", list(BUILDERS))
def test_both_depths_plan_the_same_steps_and_sample_the_same_tokens(
        built, policy):
    """With sampling on, a token depends on the order of the key's splits
    and on the tick its row ran in: both are the same at either depth."""
    cfg, params = built(policy)
    sampling = dict(greedy=False,
                    sampling=SamplingConfig(temperature=0.9, top_k=20,
                                            seed=11))
    runs = []
    for depth in (0, 1):
        got, t = _served(_engine(cfg, params, **sampling), _prompts(),
                         BUDGETS, depth)
        runs.append((got, t.engine.sched_stats()))
        _balanced(t.engine)
    assert runs[0][0] == runs[1][0]
    for key in ("ticks", "prefill_tokens_total", "decode_tokens_total"):
        if key in runs[0][1]:
            assert runs[0][1][key] == runs[1][1][key], key


@pytest.mark.parametrize("depth", [0, 1])
def test_step_called_directly_returns_what_its_depth_says(built, depth):
    cfg, params = built("dense")
    eng = _engine(cfg, params)
    eng.depth = depth
    eng.admit(1, _prompts((6,))[0])
    first = eng.step()                 # the prompt's chunk: token 1 sampled
    second = eng.step()                # a decode row: token 2 sampled
    if depth == 0:
        assert set(first) == {1} and set(second) == {1}
        assert eng.state.get(1).generated == [first[1], second[1]]
    else:
        assert first == {} and set(second) == {1}
        assert eng.state.get(1).generated == [second[1]]
        assert eng.state.get(1).in_flight == 1
        assert eng.state.get(1).total_tokens == 6 + 2
    eng.collect()
    assert eng.state.get(1).in_flight == 0
    assert len(eng.flush(1)) == 2


# --- (b) no row past the end --------------------------------------------------

@pytest.mark.parametrize("policy", list(BUILDERS))
def test_a_sequence_ended_by_length_gets_no_row_after_its_last_token(
        built, policy):
    cfg, params = built(policy)
    eng = _engine(cfg, params)
    eng.depth = 1
    eng.admit(1, _prompts((9,))[0], max_new_tokens=4)
    rows = 0
    for _ in range(12):
        eng.step()
        rows += eng.last_step_counters["decode_tokens"]
    # the chunk samples the first token, three decode rows the rest
    assert rows == 3 and eng.rows_dropped == 0
    assert not eng.has_work() and not eng._pending
    assert len(eng.state.get(1).generated) == 4
    eng.finish(1)
    assert list(eng.reap_finished()) == [1]
    _balanced(eng)


@pytest.mark.parametrize("depth,dropped", [(0, 0), (1, 1)])
def test_eos_costs_at_most_one_row_and_its_token_goes_nowhere(
        built, depth, dropped):
    cfg, params = built("dense")
    prompt = _prompts((10,))[0]
    plain = _engine(cfg, params).generate(prompt, max_new_tokens=8)
    eos = plain[3]                       # first seen at the 3rd or 4th token
    ends = plain.index(eos, 1) if eos in plain[1:] else 3
    t = Ticked(_engine(cfg, params, eos_token_id=eos), depth)
    reaped = {}
    reap = t.engine.reap_finished

    def recording():
        got = reap()
        reaped.update(got)
        return got
    t.engine.reap_finished = recording
    req = t.server.submit(prompt, max_new_tokens=8)
    (got,) = t.run([req])
    t.close()
    assert got == plain[:ends + 1] and got[-1] == eos
    assert req.finish_reason == "eos"
    assert reaped == {req.uid: got}
    assert t.engine.rows_dropped == dropped
    assert t.server.metrics.snapshot()["rows_dropped"] == dropped
    _balanced(t.engine)


# --- (c) flush, cancel, a deadline and a fault while a step is pending --------

def _pending_server(built, policy="dense", n=3, **serving):
    cfg, params = built(policy)
    t = Ticked(_engine(cfg, params), **serving)
    reqs = [t.server.submit(p, max_new_tokens=12)
            for p in _prompts((7, 13, 21)[:n])]
    t.tick(4)
    assert t.engine._pending and all(r.tokens for r in reqs)
    return t, reqs


@pytest.mark.parametrize("policy", list(BUILDERS))
def test_flush_with_a_row_in_flight_collects_it_first(built, policy):
    t, reqs = _pending_server(built, policy)
    uid = reqs[0].uid
    seq = t.engine.state.get(uid)
    assert seq.in_flight == 1
    sent = [len(r.tokens) for r in reqs]
    flushed = t.engine.flush(uid)
    assert len(flushed) == sent[0] + 1
    assert flushed[:sent[0]] == reqs[0].tokens
    assert not t.engine._pending and uid not in t.engine._ready
    # the others' tokens of that step come with the next step's
    t.tick()
    assert [len(r.tokens) for r in reqs[1:]] == [n + 1 for n in sent[1:]]
    reqs[0].cancel()
    t.run(reqs)
    t.close()
    _balanced(t.engine)


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_ended_with_a_row_in_flight_is_reaped_a_tick_later(
        built, how):
    t, reqs = _pending_server(built)
    victim = reqs[1]
    sent = len(victim.tokens)
    if how == "cancel":
        assert t.server.cancel(victim.uid)
    else:
        victim.deadline = time.monotonic() - 1.0
    t.tick()
    want = RequestState.CANCELLED if how == "cancel" \
        else RequestState.TIMED_OUT
    assert victim.state is want
    # its row of the step before was delivered or dropped, never both, and
    # nothing reaches it after its end
    assert len(victim.tokens) in (sent, sent + 1)
    ended_with = len(victim.tokens)
    got = t.run(reqs)
    t.close()
    assert len(victim.tokens) == ended_with
    assert [len(g) for g in (got[0], got[2])] == [12, 12]
    assert t.engine.rows_dropped >= 1
    _balanced(t.engine)


@pytest.mark.parametrize("policy", list(BUILDERS))
def test_a_read_that_raises_names_its_step_and_takes_the_rows_back(
        built, policy, monkeypatch):
    """A fault surfaces where a step's tokens are read, one tick after its
    dispatch: the engine takes every pending step back, the server evicts a
    request OF THAT STEP, and every token still comes out as it would have."""
    cfg, params = built(policy)
    prompts = _prompts((7, 13, 21))
    want = [_engine(cfg, params).generate(p, max_new_tokens=12, uid=i)
            for i, p in enumerate(prompts)]
    t = Ticked(_engine(cfg, params), poison_retry_budget=3)
    reqs = [t.server.submit(p, max_new_tokens=12) for p in prompts]
    t.tick(4)
    # a late arrival, admitted in the tick whose collection will fail: it is
    # in the step just planned, not in the one that failed
    late = t.server.submit(_prompts((9,), seed=5)[0], max_new_tokens=4)
    real, calls = jax.device_get, []

    def flaky(x):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("connection reset by peer")
        return real(x)
    monkeypatch.setattr(jax, "device_get", flaky)
    t.tick()
    fault = t.engine.last_fault
    assert fault is not None and late.uid not in fault["uids"]
    assert set(fault["uids"]) == {r.uid for r in reqs}
    assert not t.engine._pending
    assert all(s.in_flight == 0 for s in t.engine.state.all())
    m = t.server.metrics.snapshot()
    assert m["engine_step_faults"] == 1
    evicted = [r for r in reqs if r.fault_count]
    assert len(evicted) >= 1 and late.fault_count == 0
    got = t.run(reqs + [late])
    t.close()
    assert got[:3] == want
    assert all(r.state is RequestState.FINISHED for r in reqs + [late])
    _balanced(t.engine)


def test_a_fault_at_dispatch_leaves_the_step_before_collected(built):
    """The chaos hook raises before ``engine.step``: the step before is
    still pending then, and the handler collects it (its tokens go out with
    the next step's) before it evicts anyone."""
    t, reqs = _pending_server(built, poison_retry_budget=3)
    sent = [len(r.tokens) for r in reqs]

    class Poison:
        on_replica_kill = None

        def __init__(self):
            self.armed = True

        def serve_slow_tick(self, tick):
            pass

        def maybe_kill_replica(self, tick, has_work):
            pass

        def serve_kv_pressure(self, tick):
            return 0.0

        def maybe_poison_serve(self, uids):
            if self.armed:
                self.armed = False
                raise RuntimeError("connection reset by peer")
    t.server.chaos = Poison()
    t.tick()
    assert not t.engine._pending and t.engine.last_fault is None
    assert t.server.metrics.snapshot()["engine_step_faults"] == 1
    got = t.run(reqs)
    t.close()
    assert [len(g) for g in got] == [12, 12, 12]
    assert all(len(g) > n for g, n in zip(got, sent))
    _balanced(t.engine)


# --- (d) the threaded loop ----------------------------------------------------

def test_the_loop_does_not_idle_with_a_step_pending(built):
    cfg, params = built("dense")
    eng = _engine(cfg, params)
    server = InferenceServer(eng, ServingConfig(idle_poll_s=0.001))
    idle_with_pending = []
    wait = server._wake.wait

    def watched(timeout=None):
        idle_with_pending.append(bool(eng._pending))
        return wait(timeout)
    server._wake.wait = watched
    server.start()
    try:
        assert eng.depth == 1 or not server.running
        reqs = [server.submit(p, max_new_tokens=n)
                for p, n in zip(_prompts(), BUDGETS)]
        for r in reqs:
            r.result(timeout=120)
        deadline = time.monotonic() + 10
        while len(idle_with_pending) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        server.stop(drain_timeout=10.0)
    assert idle_with_pending and not any(idle_with_pending)
    assert eng.depth == 0 and not eng._pending
    assert [len(r.tokens) for r in reqs] == list(BUDGETS)
    assert server.metrics.snapshot()["ticks_dispatched_ahead"] > 0
    _balanced(eng)


def test_the_depth_is_no_configuration_key():
    from deepspeed_tpu.serving.server import SCHEDULER_DEFAULTS
    for cls in (V2EngineConfig, ServingConfig, SchedulerConfig):
        names = {f.name for f in dataclasses.fields(cls)}
        assert not names & {"depth", "pipeline_depth", "steps_in_flight"}, cls
    assert "depth" not in SCHEDULER_DEFAULTS


# --- (e) spans -----------------------------------------------------------------

@pytest.fixture
def tracing():
    t = get_tracer()
    was = t.enabled
    t.configure(enabled=True)
    t.clear()
    yield t
    t.configure(enabled=was)
    t.clear()


def _traced_run(built, tracing, policy="dense"):
    from benchmarks.harness import program_events as pe
    cfg, params = built(policy)
    got, t = _served(_engine(cfg, params), _prompts(), BUDGETS)
    evs = [pe.Event(e[1], e[4], e[5], e[6], e[7])
           for e in tracing.events_snapshot() if e[3] == "X"]
    return evs, pe, t


def test_the_wait_of_a_loop_closes_after_that_loops_dispatch(built, tracing):
    evs, pe, _ = _traced_run(built, tracing)
    ticks = pe.by_tick(evs)
    seen = 0
    for number, spans in ticks.items():
        waits = [e for e in spans if e.name == pe.DECODE_WAIT]
        dispatches = [e for e in spans if e.name == "serve/decode_dispatch"]
        # a tick that decodes beside chunks dispatches its rows in its last
        # chunk's program, and that chunk's span holds the wait
        fused = [e for e in spans if e.name == pe.PREFILL_CHUNK
                 and e.arg("fused_rows")]
        if waits and dispatches:
            seen += 1
            assert max(d.end for d in dispatches) <= min(w.start
                                                         for w in waits)
            (decode,) = [e for e in spans if e.name == pe.STEP_DECODE]
            assert all(decode.start <= e.start and e.end <= decode.end + 1e-6
                       for e in waits + dispatches)
        elif waits and fused:
            seen += 1
            (chunk,) = fused
            assert not [e for e in spans if e.name == pe.STEP_DECODE]
            assert all(chunk.start <= w.start and w.end <= chunk.end + 1e-6
                       for w in waits)
    assert seen >= max(BUDGETS) - 2


def test_ahead_reads_1_from_the_second_tick_on(built, tracing):
    evs, pe, t = _traced_run(built, tracing)
    # the spans of ticks that dispatched (a tick that only collects the
    # last step stamps a decode span with no batch, and ``ahead`` 0)
    work = sorted((e for e in evs if e.name == pe.PREFILL_CHUNK
                   or e.name == pe.STEP_DECODE and e.arg("batch")),
                  key=lambda e: e.start)
    first_tick = work[0].arg("tick")
    assert all(e.arg("ahead") == 0 for e in work
               if e.arg("tick") == first_tick)
    later = [e for e in work if e.arg("tick") != first_tick]
    assert later and all(e.arg("ahead") == 1 for e in later)
    assert sum(e.arg("rows_dropped", 0) for e in work) == 0
    steps = {e.arg("tick") for e in later}
    assert t.server.metrics.snapshot()["ticks_dispatched_ahead"] == len(steps)


@pytest.mark.parametrize("policy", ["dense", "latent"])
def test_one_interval_of_device_work_a_step(built, tracing, policy):
    """The accepted readers take what lies between the ends of two waits as
    one tick's device work: with the wait labelled by the loop that makes
    it, every dispatching tick after the first still gets one interval of
    its own, the intervals tile, and none holds two steps."""
    from benchmarks.harness import costs_latent
    evs, pe, _ = _traced_run(built, tracing, policy)
    loop = [e for e in evs if e.arg("tick") is not None]
    found = costs_latent.ticks_with_device_work(
        loop, (min(e.start for e in loop), max(e.end for e in loop)))
    dispatching = sorted({e.arg("tick") for e in loop
                          if e.name in costs_latent.COUNTED_SPANS})
    waiting = {e.arg("tick") for e in loop if e.name == pe.DECODE_WAIT}
    assert len(found) == len([n for n in dispatching if n in waiting])
    assert len(found) >= max(BUDGETS) - 2
    intervals = [interval for interval, _ in found]
    for (_, hi), (lo, _) in zip(intervals, intervals[1:]):
        assert lo >= hi - 1e-9           # they never overlap
    for (lo, hi), work in found:
        # the spans of exactly one tick, and one decode step at most
        assert len({e.arg("tick") for e in work}) == 1
        assert sum(e.name == pe.STEP_DECODE for e in work) <= 1
    if policy == "latent":
        # the counts a step's programs made are read with that step's
        # tokens and ride on the spans of the tick that read them
        counts = costs_latent.step_counts([e for _, w in found for e in w])
        assert counts and all(rows > 0 for rows, _ in counts)


def test_the_threaded_loop_stamps_the_same_spans(built, tracing):
    from benchmarks.harness import program_events as pe
    cfg, params = built("dense")
    server = InferenceServer(_engine(cfg, params),
                             ServingConfig(idle_poll_s=0.001)).start()
    try:
        reqs = [server.submit(p, max_new_tokens=n)
                for p, n in zip(_prompts(), BUDGETS)]
        for r in reqs:
            r.result(timeout=120)
    finally:
        server.stop(drain_timeout=10.0)
    evs = [pe.Event(e[1], e[4], e[5], e[6], e[7])
           for e in tracing.events_snapshot() if e[3] == "X"]
    # the span that holds a tick's wait: its decode span, or its last chunk's
    # where the decode rows rode in that chunk's program
    decodes = [e for e in pe.loop_thread(evs) if e.name == pe.STEP_DECODE
               or e.name == pe.PREFILL_CHUNK and e.arg("fused_rows")]
    assert len(decodes) >= max(BUDGETS) - 1
    ahead = [e.arg("ahead") for e in decodes]
    assert sum(ahead) >= 0.9 * (len(ahead) - 1)
    assert all(e.arg("rows_dropped") == 0 for e in decodes)
    bubbles = pe.decode_bubbles(pe.loop_thread(evs))
    assert bubbles and all(b >= 0 for b in bubbles)


# --- (f) starved: had the device run dry when the next step was queued ---------

class _Sampled:
    """A pending step's sampled tokens behind a double that says what the
    test tells it to when asked whether they are there yet."""

    def __init__(self, array, ready):
        self.array, self.ready = array, ready

    def is_ready(self):
        return self.ready

    def __array__(self, *args, **kwargs):
        return np.asarray(self.array)


class _Mute:
    """The same with no ``is_ready`` to ask."""

    def __init__(self, array):
        self.array = array

    def __array__(self, *args, **kwargs):
        return np.asarray(self.array)


def _hold(rec, double):
    """Put what the step sampled last behind ``double(array)``: its decode's
    tokens, or its last chunk's where it decoded nothing."""
    if rec.decode_sampled is not None:
        rec.decode_sampled = double(rec.decode_sampled)
    else:
        seq, start, length, sampled = rec.chunks[-1]
        rec.chunks[-1] = (seq, start, length, double(sampled))


@pytest.mark.parametrize("case,double,want", [
    ("finished", lambda a: _Sampled(a, True), 1),
    ("running", lambda a: _Sampled(a, False), 0),
    ("cannot_say", _Mute, None)])
def test_starved_says_whether_the_pending_step_had_finished(
        built, tracing, case, double, want):
    cfg, params = built("dense")
    prompt = _prompts((5,))[0]
    alone = _engine(cfg, params).generate(prompt, max_new_tokens=8, uid=0)
    tracing.clear()
    t = Ticked(_engine(cfg, params))
    req = t.server.submit(prompt, max_new_tokens=8)
    t.tick()        # the prompt's chunk: nothing was pending before it
    first = t.engine.last_step_counters
    assert first["ahead"] == 0 and "starved" not in first
    held = 4        # the first of them finds a chunk's token, the rest a decode's
    for _ in range(held):
        _hold(t.engine._pending[-1], double)
        t.tick()
        counters = t.engine.last_step_counters
        assert counters["ahead"] == 1
        assert counters.get("starved") == want
    assert t.server.metrics.snapshot()["ticks_device_starved"] == \
        (held if want == 1 else 0)
    assert t.server.metrics.snapshot()["ticks_dispatched_ahead"] == held
    assert t.run([req]) == [alone]      # asking changed no token
    t.close()
    from benchmarks.harness import program_events as pe
    decodes = sorted((e for e in tracing.events_snapshot()
                      if e[1] == pe.STEP_DECODE and (e[7] or {}).get("batch")),
                     key=lambda e: e[4])
    assert len(decodes) >= held
    for e in decodes[:held]:
        assert e[7]["ahead"] == 1 and e[7].get("starved") == want


def test_a_real_array_that_has_been_waited_for_reads_starved(built):
    """What the engine hands itself can be asked: ``jax.Array.is_ready``."""
    cfg, params = built("dense")
    t = Ticked(_engine(cfg, params))
    req = t.server.submit(_prompts((5,))[0], max_new_tokens=6)
    t.tick(2)
    for _ in range(3):
        rec = t.engine._pending[-1]
        assert rec.decode_sampled.is_ready() in (True, False)
        jax.block_until_ready(rec.decode_sampled)
        t.tick()
        assert t.engine.last_step_counters["starved"] == 1
    assert t.server.metrics.snapshot()["ticks_device_starved"] >= 3
    t.run([req])
    t.close()


def test_starved_is_absent_at_depth_0(built, tracing):
    cfg, params = built("dense")
    got, t = _served(_engine(cfg, params), _prompts(), BUDGETS, depth=0)
    assert [len(g) for g in got] == list(BUDGETS)
    spans = [e for e in tracing.events_snapshot()
             if e[1] in ("serve/step_decode", "serve/prefill_chunk")]
    assert spans and all(e[7]["ahead"] == 0 and "starved" not in e[7]
                         for e in spans)
    assert "starved" not in t.engine.last_step_counters
    m = t.server.metrics.snapshot()
    assert m["ticks_device_starved"] == 0 == m["ticks_dispatched_ahead"]


def test_starved_rides_on_the_chunks_of_a_tick_that_decodes_nothing(
        built, tracing):
    """Two prompts of one chunk each, a tick apart: the second's tick
    dispatches a chunk and a decode, a third prompt's a chunk alone."""
    cfg, params = built("dense")
    eng = _engine(cfg, params, scheduler=SchedulerConfig(
        max_tokens_per_step=8, max_decode_batch=8, prefill_buckets=(8,)))
    t = Ticked(eng)
    req = t.server.submit(_prompts((20,))[0], max_new_tokens=2)
    t.tick()                    # chunk 1 of 3: ends no prompt, samples nothing
    assert "starved" not in eng.last_step_counters
    t.tick()                    # chunk 2: the step before it sampled nothing
    assert eng.last_step_counters["ahead"] == 1
    assert "starved" not in eng.last_step_counters
    t.tick()                    # chunk 3 ends the prompt and samples
    _hold(eng._pending[-1], lambda a: _Sampled(a, True))
    t.tick()                    # the decode finds chunk 3's token there
    assert eng.last_step_counters["starved"] == 1
    t.run([req])
    t.close()
    chunks = [e[7] for e in tracing.events_snapshot()
              if e[1] == "serve/prefill_chunk"]
    assert len(chunks) == 3 and all("starved" not in c for c in chunks)
    assert [c["ahead"] for c in chunks] == [0, 1, 1]
