"""The KV pool is updated in place: the three served step programs consume
the pool they are given (``generic_decode`` donates ``cache_data``) and the
engine binds what comes back at once.

(a) every pool leaf is aliased in the compiled program and the array passed
in reads deleted after the call; (b) through the engine the tokens equal
those of the same functions jitted without donation; (c) a fault between a
donated call and the step's end leaves the engine on a live pool, alone and
behind a real ``InferenceServer``; (d) ``serve/kv_alias`` says so, once per
compiled step program, at no second compile.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import engine_v2, generic_decode as gd
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2, V2EngineConfig
from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache, KVCacheConfig
from deepspeed_tpu.inference.v2.modules import policy_for
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import (TINY_LLAMA, LlamaConfig,
                                        LlamaForCausalLM, random_tokens)
from deepspeed_tpu.models.mixtral import TINY_MIXTRAL, MixtralForCausalLM
from deepspeed_tpu.telemetry import names
from deepspeed_tpu.telemetry.tracer import get_tracer

STEP_FNS = {"prefill": gd.prefill_chunk_g, "verify": gd.verify_chunk_g,
            "decode": gd.decode_step_g}
STATIC = ("policy", "cfg", "block_size", "attn_impl")


def _llama(**over):
    return LlamaConfig(**{**TINY_LLAMA.__dict__, "dtype": jnp.float32,
                          "max_seq_len": 512, **over})


def _mixtral():
    return dataclasses.replace(
        TINY_MIXTRAL,
        base=dataclasses.replace(TINY_MIXTRAL.base, dtype=jnp.float32),
        moe=dataclasses.replace(TINY_MIXTRAL.moe, dtype=jnp.float32))


MODELS = {"llama": (_llama, LlamaForCausalLM),
          "llama-window": (lambda: _llama(sliding_window=24),
                           LlamaForCausalLM),
          "mixtral": (_mixtral, MixtralForCausalLM)}


@pytest.fixture(scope="module")
def built():
    """name -> (cfg, params), each model initialised once."""
    memo = {}

    def get(name):
        if name not in memo:
            make_cfg, cls = MODELS[name]
            cfg = make_cfg()
            vocab = getattr(cfg, "base", cfg).vocab_size
            memo[name] = (cfg, cls(cfg).init(
                jax.random.PRNGKey(0),
                random_tokens(1, 8, vocab_size=vocab))["params"])
        return memo[name]
    return get


def _engine(cfg, params, kv_num_blocks=64, **over):
    return InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=kv_num_blocks,
        scheduler=SchedulerConfig(max_tokens_per_step=64,
                                  prefill_buckets=(16, 32, 64)), **over))


def _step_args(fn_name, cfg, params, dtype):
    """(args, kwargs) of one small call of a step program on a fresh pool."""
    policy = policy_for(cfg)
    spec = policy.cache_spec(cfg)
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=spec.num_layers, num_kv_heads=spec.num_kv_heads,
        head_dim=spec.head_dim, block_size=16, num_blocks=16, dtype=dtype))
    kw = dict(policy=policy, cfg=cfg, block_size=16, attn_impl="gather")
    if fn_name == "decode":
        tail = (jnp.asarray([3, 0], jnp.int32), jnp.asarray([5, 0], jnp.int32),
                jnp.asarray([[0, 1], [15, 15]], jnp.int32),
                jnp.asarray([True, False]))
    else:
        tail = (jnp.arange(16, dtype=jnp.int32), 0,
                jnp.asarray([0, 1], jnp.int32), 11)
    return (params, kv.pool) + tail, kw


# --- (a) the compiled program aliases the pool, the call consumes it --------

@pytest.mark.parametrize("pages", ["plain", "fp8-scaled"])
@pytest.mark.parametrize("fn_name", sorted(STEP_FNS))
def test_step_program_aliases_and_consumes_the_pool(built, fn_name, pages):
    cfg, params = built("llama")
    dtype = jnp.float8_e4m3fn if pages == "fp8-scaled" else jnp.float32
    args, kw = _step_args(fn_name, cfg, params, dtype)
    fn = STEP_FNS[fn_name]
    leaves = jax.tree.leaves(args[1])
    assert len(leaves) == (2 if pages == "fp8-scaled" else 1)
    pool_bytes = sum(x.nbytes for x in leaves)

    compiled = fn.lower(*args, **kw).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    header = compiled.as_text().splitlines()[0]
    assert header.count("may-alias") + header.count("must-alias") \
        == len(leaves), header

    out, pool, _ = fn(*args, **kw)
    assert all(x.is_deleted() for x in leaves)
    got = jax.tree.leaves(pool)
    assert [(x.shape, x.dtype) for x in got] == \
        [(x.shape, x.dtype) for x in leaves]
    assert not any(x.is_deleted() for x in got)
    assert np.isfinite(np.asarray(out)).all()


# --- (b) same tokens as the same functions without donation -----------------

def _undonated(monkeypatch):
    """The engine's three step functions, jitted again without donation."""
    for name in ("prefill_chunk_g", "verify_chunk_g", "decode_step_g"):
        plain = jax.jit(getattr(gd, name).__wrapped__, static_argnames=STATIC)
        monkeypatch.setattr(engine_v2, name, plain)


def _mixed_ticks(eng, prompts, steps, check=None):
    """Admit the first prompt, then the others while it decodes: ticks that
    prefill, ticks that decode, and ticks that do both."""
    got = {u: [] for u in range(len(prompts))}

    def note(out):
        for u, tok in out.items():
            got[u].append(tok)
        if check is not None:
            check(eng)
    note(eng.put([0], [prompts[0]]))
    note(eng.step())
    note(eng.put(list(range(1, len(prompts))), prompts[1:]))
    for _ in range(steps):
        note(eng.step())
    return got


def _prompts(cfg, sizes=(40, 70, 9)):
    vocab = getattr(cfg, "base", cfg).vocab_size
    rng = np.random.default_rng(28)
    return [[int(t) for t in rng.integers(0, vocab, n)] for n in sizes]


@pytest.mark.parametrize("model", sorted(MODELS))
def test_engine_tokens_equal_the_undonated_functions(built, monkeypatch, model):
    cfg, params = built(model)
    prompts = _prompts(cfg)
    consumed = []

    def live(eng):
        assert not eng.kv.data.is_deleted()

    eng = _engine(cfg, params)
    before = eng.kv.data
    donated = _mixed_ticks(eng, prompts, 6, check=live)
    consumed.append(before.is_deleted())

    with monkeypatch.context() as m:
        _undonated(m)
        ref = _engine(cfg, params)
        before = ref.kv.data
        plain = _mixed_ticks(ref, prompts, 6, check=live)
        consumed.append(before.is_deleted())

    assert consumed == [True, False]     # the comparison had two sides
    assert donated == plain
    assert all(len(t) >= 5 for t in donated.values())


def test_speculative_verify_rebinds_the_pool(built):
    cfg, params = built("llama")
    prompt = [5, 6, 7, 8] * 6
    plain = _engine(cfg, params).generate(prompt, max_new_tokens=8)
    eng = _engine(cfg, params, speculative_k=4)
    # an oracle proposer, half of whose proposals are wrong: verify steps
    # that accept and verify steps that reject
    eng._propose = lambda seq: [
        t + (i % 2) for i, t in enumerate(
            plain[len(seq.generated):len(seq.generated) + 4])]
    assert eng.generate(prompt, max_new_tokens=8) == plain
    assert eng.speculative_stats()["steps"] > 0
    assert not eng.kv.data.is_deleted()


# --- (c) a fault after a donated call leaves the engine on a live pool ------

def _fault_once(eng, monkeypatch, at_call):
    """The sampler raises on its ``at_call``-th call: after the step program
    consumed the pool, before the step's end."""
    real, calls = eng._sample_dispatch, []

    def sampler(logits, rows):
        calls.append(1)
        if len(calls) == at_call:
            raise RuntimeError("connection reset by peer")
        return real(logits, rows)
    monkeypatch.setattr(eng, "_sample_dispatch", sampler)
    return calls


@pytest.mark.parametrize("pages", ["model", "fp8"])
def test_fault_after_a_donated_call_leaves_a_live_pool(built, monkeypatch,
                                                       pages):
    cfg, params = built("llama")
    prompts = _prompts(cfg, sizes=(30, 12))

    def run(fault):
        eng = _engine(cfg, params, kv_cache_dtype=pages)
        if fault:
            _fault_once(eng, monkeypatch, at_call=4)
        eng.put([0, 1], prompts)
        faults = 0
        while min(len(eng.state.get(u).generated) for u in (0, 1)) < 8:
            try:
                eng.step()
            except RuntimeError:
                faults += 1
            assert not any(x.is_deleted()
                           for x in jax.tree.leaves(eng.kv.pool))
        return faults, [eng.state.get(u).generated[:8] for u in (0, 1)]

    assert run(True) == (1, run(False)[1])


def test_server_recovers_from_a_fault_after_a_donated_call(built, monkeypatch):
    """``test_serve_siege``'s transient-fault drill with a real engine: the
    server steps again after the fault, on the pool the faulted step left."""
    from deepspeed_tpu.serving.request import RequestState
    from deepspeed_tpu.serving.server import (InferenceServer, ServingConfig,
                                              _EngineStepError)
    cfg, params = built("llama")
    prompt = _prompts(cfg, sizes=(20,))[0]

    def serve(fault):
        eng = _engine(cfg, params)
        server = InferenceServer(eng, ServingConfig(
            recover_clean_steps=3, poison_retry_budget=1, idle_poll_s=0.001))
        if fault:
            _fault_once(eng, monkeypatch, at_call=3)
        req = server.submit(prompt, max_new_tokens=6)
        for _ in range(40):
            try:
                server._serve_once()
            except _EngineStepError as e:
                server._on_step_fault(e)
            assert not eng.kv.data.is_deleted()
            if req.state.terminal:
                break
        assert req.state == RequestState.FINISHED
        return server, list(req.result())

    server, tokens = serve(True)
    snap = server.metrics.snapshot()
    assert snap["engine_step_faults"] == 1 and snap["degraded_latches"] == 0
    assert server._degraded is None
    assert tokens == serve(False)[1]


# --- (d) the counter that says it engaged -----------------------------------

def test_kv_alias_is_registered():
    assert names.TRACE_NAMES["serve/kv_alias"] == ("instant",)


@pytest.fixture
def backend_compiles():
    """Names of jax's backend-compile events, as they happen."""
    from jax._src import monitoring
    seen = []

    def listen(name, *args, **kwargs):
        if name.endswith("backend_compile_duration"):
            seen.append(name)
    monitoring.register_event_duration_secs_listener(listen)
    yield seen
    monitoring.unregister_event_duration_listener(listen)


@pytest.mark.parametrize("pages", ["model", "fp8"])
def test_kv_alias_once_per_compiled_step_program(built, backend_compiles,
                                                 pages):
    cfg, params = built("llama")
    tracer = get_tracer()

    def events():
        return [e for e in tracer.export_chrome()["traceEvents"]
                if "id" in e.get("args", {})]

    def run(num_blocks, traced):
        """(backend compiles, engine, new events) of a run whose pool shape
        no other test has compiled."""
        was = tracer.enabled
        tracer.configure(enabled=traced)
        try:
            compiles0 = len(backend_compiles)
            last = max((e["args"]["id"] for e in events()), default=0)
            eng = _engine(cfg, params, kv_num_blocks=num_blocks,
                          kv_cache_dtype=pages)
            eng.put([0], [list(range(1, 21))])
            for _ in range(3):
                eng.step()
            return (len(backend_compiles) - compiles0, eng,
                    [e for e in events() if e["args"]["id"] > last])
        finally:
            tracer.configure(enabled=was)

    run(35, traced=False)     # whatever compiles once a process, whatever the pool
    quiet, _, none = run(37, traced=False)
    loud, eng, new = run(39, traced=True)
    alias = [e["args"] for e in new if e["name"] == "serve/kv_alias"]
    compiled = [e["args"]["fn"] for e in new if e["name"] == "xla/compile"
                and e["args"]["fn"].startswith("generic_decode.")]
    assert none == []
    # saying it cost no compile: as many with the event as without
    assert loud == quiet
    assert len(compiled) == 2                    # one prefill, one decode
    assert sorted(a["fn"] for a in alias) == sorted(compiled)
    pool_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.kv.pool))
    for a in alias:
        assert a["donated"] is True
        assert a["alias_bytes"] == a["pool_bytes"] == pool_bytes
