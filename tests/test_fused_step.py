"""A tick's decode rows ride in its last chunk's step program: one forward
pass a tick, every weight read once (``generic_decode.prefill_chunk_g`` with
a decode half; ``engine_v2._dispatch``).

The step program for every page kind the repo serves against the two programs
it replaces (``prefill_chunk_g`` alone, then ``decode_step_g``) on the same
inputs; then the engine: what a tick dispatches, what it commits at either
depth, what it stamps, and that the warm-up of the two kinds of tick alone
leaves nothing to compile for a tick of both. CPU, float32, the gather path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2 import generic_decode as gd
from deepspeed_tpu.inference.v2.sampling import feed_tokens
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.telemetry.compiles import compiles_total
from test_step_in_flight import (BUILDERS, BUDGETS, _prompts, _served,
                                 tracing)  # noqa: F401 (a fixture)
from test_step_in_flight import _engine as _engine_in_flight

BLOCK = 8
#: float32 on the CPU: a row's matmuls in a step of Tb + B rows and in one of
#: B differ in the order of their sums at most
TOL = 2e-5


def _state():
    from deepspeed_tpu.models import granite_hybrid as gh
    cfg = dataclasses.replace(gh.TINY_GRANITE_HYBRID, dtype=jnp.float32)
    params = gh.GraniteHybridForCausalLM(cfg).init(
        jax.random.PRNGKey(0),
        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return cfg, params


def _split():
    """MiMo-V2's toy: a K pool and a V pool a layer kind (keys of 24 beside
    values of 16, 1 and 2 KV heads), a window of 24 and drawn sinks."""
    from test_mimo_v2 import build
    cfg, _, params = build()
    return cfg, params


#: page kind -> (what builds the model, engine options): head pages, the same
#: in fp8 under scales, latent pages, pages by layer kind behind a window, the
#: same with keys wider than values and a sink logit, a state slot beside pages
KINDS = {
    "head_pages": (BUILDERS["dense"], {}),
    "scaled_head_pages": (BUILDERS["dense"], {"kv_cache_dtype": "fp8"}),
    "latent_pages": (BUILDERS["latent"], {}),
    "pages_by_layer_kind": (BUILDERS["two_page_kinds"], {}),
    "split_head_pages": (_split, {}),
    "state_slots": (_state, {}),
}


@pytest.fixture(scope="module")
def built():
    made = {}

    def get(kind):
        build, options = KINDS[kind]
        if build not in made:
            made[build] = build()
        return made[build] + (options,)
    return get


def _engine(cfg, params, **over):
    """``test_step_in_flight``'s engine (blocks of 8, a step of 32 tokens,
    context buckets of 4, 8 and 16 blocks) with a largest decode bucket of 4
    rows, which is then the fused half's."""
    return _engine_in_flight(cfg, params, **{
        "max_tracked_sequences": 8, "attn_impl": "gather",
        "decode_batch_buckets": (1, 2, 4),
        "scheduler": SchedulerConfig(max_tokens_per_step=32,
                                     max_decode_batch=4,
                                     prefill_buckets=(8, 16, 32)), **over})


# --- the step program ---------------------------------------------------------

def _half_shape(eng):
    """The decode half's (rows, table blocks): the engine's own; over a state
    kind, whose ticks keep their two programs, what it would give one."""
    return eng._fused_decode or (4, 16)


def _padding_half(eng):
    if eng._no_decode is not None:
        return eng._no_decode
    b, mb = _half_shape(eng)
    return (jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
            eng._decode_tables((), b, mb), jnp.zeros((b,), bool))


def _a_chunk_beside_decoders(eng, decoders=(11, 30, 19), prompt=21, start=0):
    """Sequences that decode (their prompts prefilled and a few tokens
    decoded: contexts past a block's edge and, where there is one, the
    window) and one admitted after them; returns what ``_dispatch`` would
    hand the step programs for the newcomer's first chunk and the decode
    batch, through the engine's own builders."""
    for uid, n in enumerate(decoders):
        eng.admit(100 + uid, _prompts((n,), seed=uid)[0])
    for _ in range(4):
        eng.step()
    seqs = eng.state.decoding()
    assert len(seqs) == len(decoders)
    eng.admit(7, _prompts((prompt,), seed=9)[0])
    seq = eng.state.get(7)
    bucket, take = 32, prompt - start
    eng._ensure_blocks(seq, prompt)
    tokens = np.zeros((bucket,), np.int32)
    tokens[:take] = seq.prompt_tokens[start:prompt]
    mb = eng._ctx_bucket_blocks(prompt)
    table = jax.tree.map(jnp.asarray,
                         eng._step_tables(seq, mb, start, bucket))
    rows, half, _, _ = eng._decode_rows(seqs, *_half_shape(eng))
    half = (feed_tokens(eng._last_tokens, jnp.asarray(rows)),) + half
    return (jnp.asarray(tokens), start, table, take), half


def _programs(eng):
    kw = dict(policy=eng.policy, cfg=eng.model_config,
              block_size=eng.kv.cfg.block_size, attn_impl="gather")

    def pool():
        return jax.tree.map(jnp.copy, eng.kv.pool)
    return kw, pool


def _live(eng, pool):
    """The pool's leaves without what padding rows write: each kind's trash
    block and the state kind's slot past the last."""
    kind = eng.kv.kind

    def cut(leaf, axis):
        return np.delete(np.asarray(leaf, np.float32), -1, axis)
    if not eng.kv.by_layer_kind:
        return [cut(x, kind.block_axis) for x in jax.tree.leaves(pool)]
    out = []
    for name, pages in kind.pages.items():
        out += [cut(x, pages.block_axis) for x in jax.tree.leaves(pool[name])]
    if kind.state is not None:
        out += [cut(x, 1) for x in jax.tree.leaves(pool["state"])]
    return out


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_fused_step_equals_the_chunk_program_then_the_decode_program(
        built, kind):
    cfg, params, options = built(kind)
    eng = _engine(cfg, params, **options)
    chunk, half = _a_chunk_beside_decoders(eng)
    kw, pool = _programs(eng)
    (logits, decoded), fused, counts = gd.prefill_chunk_g(
        params, pool(), *chunk, half, **kw)
    want, two, c1 = gd.prefill_chunk_g(params, pool(), *chunk, **kw)
    want_decoded, two, c2 = gd.decode_step_g(params, two, *half, **kw)
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=TOL)
    live = np.asarray(half[3])
    assert live.sum() == 3 and decoded.shape[0] == _half_shape(eng)[0]
    np.testing.assert_allclose(np.asarray(decoded)[live],
                               np.asarray(want_decoded)[live],
                               atol=TOL, rtol=TOL)
    # fp8 pages round a value that differs in its last bits to another code
    tol = 0.07 if kind == "scaled_head_pages" else TOL
    for got, ref in zip(_live(eng, fused), _live(eng, two)):
        np.testing.assert_allclose(got, ref, atol=tol, rtol=tol)
    # one counts vector: a fused tick's experts are counted once, the rows of
    # both halves together
    assert counts.shape == c1.shape
    if counts.size:
        assert int(counts[0]) == int(c1[0]) + int(c2[0])      # expert_rows
        assert int(counts[1]) <= int(c1[1]) + int(c2[1])      # touched once


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_decode_half_of_padding_alone_is_the_chunk_program(built, kind):
    cfg, params, options = built(kind)
    eng = _engine(cfg, params, **options)
    chunk, _ = _a_chunk_beside_decoders(eng)
    kw, pool = _programs(eng)
    before = _live(eng, eng.kv.pool)
    (logits, decoded), fused, counts = gd.prefill_chunk_g(
        params, pool(), *chunk, _padding_half(eng), **kw)
    want, alone, c1 = gd.prefill_chunk_g(params, pool(), *chunk, **kw)
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=TOL)
    assert decoded.shape[0] == _half_shape(eng)[0]
    np.testing.assert_array_equal(counts, c1)
    touched = 0
    for got, ref, was in zip(_live(eng, fused), _live(eng, alone), before):
        # the live blocks and slots hold what the chunk alone wrote there
        # (the chunk's own rows among them), bit for bit: writes are copies
        np.testing.assert_array_equal(got, ref)
        touched += int((got != was).sum())
    assert touched            # the chunk did write


def test_each_kind_names_the_operands_that_are_rows():
    """The split is never guessed from a leading axis: an up-projection of
    as many rows as the step has would be cut otherwise."""
    from deepspeed_tpu.inference.v2 import kv_cache as kc
    assert kc._HeadPages().row_operands(0) == 3
    assert kc._ScaledHeadPages().row_operands(0) == 3
    assert kc._LatentPages().row_operands(0) == 3
    slot = kc.StateSlotShape(heads=2, head_dim=4, d_state=4, conv_width=4,
                             conv_channels=16)
    mixed = kc._LayerKindPages((None, 8, None), layer_kinds=(
        "state", "window", "full"), state_slot=slot)
    assert [mixed.row_operands(i) for i in range(3)] == [2, 3, 3]


# --- the engine's tick --------------------------------------------------------

@pytest.fixture
def dispatched(monkeypatch):
    """Every step program the engine dispatches, by name, in order."""
    seen = []
    for name in ("prefill_chunk_g", "decode_step_g"):
        def spy(*args, _inner=getattr(engine_v2, name), _name=name, **kw):
            seen.append(_name)
            return _inner(*args, **kw)
        monkeypatch.setattr(engine_v2, name, spy)
    return seen


def _spans(tracing, tick):
    return [(e[1], e[7]) for e in tracing.events_snapshot()
            if e[3] == "X" and (e[7] or {}).get("tick") == tick]


def test_a_tick_of_chunks_and_decoders_dispatches_one_program_a_chunk(
        built, dispatched, tracing):
    cfg, params, _ = built("head_pages")
    eng = _engine(cfg, params)
    eng.admit(1, _prompts((9,))[0])
    eng.admit(2, _prompts((14,))[0])
    eng.step()                                   # two chunks, nobody decodes
    assert dispatched == ["prefill_chunk_g"] * 2
    assert eng.last_step_counters["decode_tokens"] == 0
    del dispatched[:]
    eng.admit(3, _prompts((5,))[0])
    eng.admit(4, _prompts((11,))[0])
    tick = eng.tick
    out = eng.step()                             # two chunks, two decoders
    assert dispatched == ["prefill_chunk_g"] * 2
    assert set(out) == {1, 2, 3, 4}              # every row's token, this tick
    assert eng.last_step_counters["decode_tokens"] == 2
    spans = _spans(tracing, tick)
    chunks = [args for name, args in spans if name == "serve/prefill_chunk"]
    assert [("fused_rows" in c) for c in chunks] == [False, True]
    assert chunks[-1]["fused_rows"] == 2
    names = {name for name, _ in spans}
    assert not names & {"serve/step_decode", "serve/decode_build",
                        "serve/decode_dispatch"}
    assert "serve/decode_wait" in names          # the last chunk holds it

    del dispatched[:]
    tick = eng.tick
    eng.step()                                   # four decoders, no chunk
    assert dispatched == ["decode_step_g"]
    assert eng.last_step_counters["chunks"] == 0
    spans = _spans(tracing, tick)
    (decode,) = [args for name, args in spans if name == "serve/step_decode"]
    assert (decode["batch"], decode["bucket"]) == (4, 4)
    assert decode["ctx_tokens"] == 11 + 16 + 6 + 12
    assert not any(name == "serve/prefill_chunk" for name, _ in spans)


def test_over_a_state_kind_a_tick_keeps_its_two_programs(built, dispatched,
                                                        tracing):
    """The fused step read under the two programs on the chip there (PERF.md
    section 6, PR 45): no chunk carries a decode half, the decode rows follow
    in ``decode_step_g`` as at the parent, and the last chunk's span says
    that none rode with it."""
    cfg, params, _ = built("state_slots")
    eng = _engine(cfg, params)
    assert eng._fused_decode is None and eng._no_decode is None
    eng.admit(1, _prompts((9,))[0])
    eng.step()
    eng.admit(2, _prompts((14,))[0])
    eng.admit(3, _prompts((5,))[0])
    del dispatched[:]
    tick = eng.tick
    out = eng.step()                             # two chunks, one decoder
    assert dispatched == ["prefill_chunk_g"] * 2 + ["decode_step_g"]
    assert set(out) == {1, 2, 3}
    spans = _spans(tracing, tick)
    chunks = [args for name, args in spans if name == "serve/prefill_chunk"]
    assert [c.get("fused_rows") for c in chunks] == [None, 0]
    (decode,) = [args for name, args in spans if name == "serve/step_decode"]
    assert (decode["batch"], decode["bucket"]) == (1, 1)


def _two_programs(params, pool, tokens, start, table, true_len, half, **kw):
    """What the parent dispatched for a chunk and the tick's decode batch."""
    logits, pool, c1 = gd.prefill_chunk_g(params, pool, tokens, start, table,
                                          true_len, **kw)
    if half is None:        # a state kind: the engine's own second program
        return logits, pool, c1
    decoded, pool, c2 = gd.decode_step_g(params, pool, *half, **kw)
    return (logits, decoded), pool, c1 + c2


@pytest.mark.parametrize("depth", [0, 1])
@pytest.mark.parametrize("kind", list(KINDS))
def test_a_served_run_commits_what_the_two_programs_commit(
        built, kind, depth, monkeypatch):
    cfg, params, options = built(kind)
    prompts = _prompts()
    got, t = _served(_engine(cfg, params, **options), prompts, BUDGETS, depth)
    stats = t.engine.sched_stats()
    with monkeypatch.context() as patch:
        patch.setattr(engine_v2, "prefill_chunk_g", _two_programs)
        want, t2 = _served(_engine(cfg, params, **options), prompts, BUDGETS,
                           depth)
    assert got == want
    assert [len(g) for g in got] == list(BUDGETS)
    assert stats == t2.engine.sched_stats()


def test_a_tick_without_chunks_hands_decode_step_g_what_the_parent_did(
        built, monkeypatch):
    """Batch and context buckets of the batch itself, not the fused half's."""
    cfg, params, _ = built("head_pages")
    eng = _engine(cfg, params)
    shapes = []

    def spy(params, pool, tokens, positions, tables, valid, **kw):
        shapes.append((tokens.shape, positions.shape, tables.shape,
                       valid.shape, np.asarray(valid).sum()))
        return gd.decode_step_g(params, pool, tokens, positions, tables,
                                valid, **kw)
    monkeypatch.setattr(engine_v2, "decode_step_g", spy)
    eng.admit(1, _prompts((9,))[0])
    eng.admit(2, _prompts((40,))[0])
    eng.admit(3, _prompts((3,))[0])
    for _ in range(3):
        eng.step()
    assert eng._fused_decode == (4, 16)
    # three decoders in a bucket of 4, the longest context 42 tokens: 8 blocks
    assert shapes[-1] == ((4,), (4,), (4, 8), (4,), 3)


def test_warm_ticks_of_one_kind_leave_a_mixed_tick_nothing_to_compile(built):
    """A chunk program's shape follows from its chunk and the engine's
    configuration alone: warmed with no decoder live, as the benchmark's
    warm-up makes them (``run_serve.warm_engine``, whose enumeration this
    follows), it is the program a tick of both dispatches; and the sampler's
    shapes for its decode half are warm with it."""
    from benchmarks.harness.run_serve import reachable_shapes
    cfg, params, _ = built("head_pages")
    eng = _engine(cfg, params)
    mix = {"prompt_tokens": {"min": 3, "max": 100},
           "output_tokens": {"max": 12}, "max_concurrency": 2}
    prefill, decode = reachable_shapes(eng.config, mix)
    # two callers: no decode batch of the largest bucket, which is the fused
    # half's, is ever warmed alone
    assert len(prefill) == 9 and len(decode) == 6
    assert max(d for d, _ in decode) == 2 < eng._fused_decode[0]
    uid = iter(range(1000, 2000))

    def prefilled(sizes):
        """Sequences of ``sizes`` admitted together with nobody decoding,
        their prompts prefilled by ticks of chunks alone."""
        assert not eng.state.decoding()
        live = [next(uid) for _ in sizes]
        for u, n in zip(live, sizes):
            eng.admit(u, [1] * n)
        while any(eng.state.get(u).in_prefill for u in live):
            eng.step()
            assert eng.last_step_counters["decode_tokens"] == 0
        return live

    for b, m in prefill:                # a chunk of bucket b that ends in m
        take = min(b, 100)
        end = max(min(m * BLOCK, 100), take)
        (u,) = prefilled([end - take] if end > take else [take])
        if end > take:
            eng.put([u], [[1] * take])
            while eng.state.get(u).in_prefill:
                eng.step()
        eng.flush(u)
    for d, m in decode:                 # a decode batch of bucket d up to m
        below = max((c for c in eng.config.ctx_block_buckets if c < m),
                    default=0)
        live = prefilled([min(max(3, below * BLOCK + 1), 100)]
                         + [3] * (d - 1))
        eng.step()
        assert eng.last_step_counters["chunks"] == 0
        assert eng.last_step_counters["decode_tokens"] == d
        for u in live:
            eng.flush(u)
    warmed = compiles_total()

    rng = np.random.default_rng(5)
    live, fused = [], 0
    for step in range(40):
        if step % 3 == 0 and len(live) < 2:
            live.append(next(uid))
            eng.admit(live[-1], rng.integers(1, 500, int(rng.integers(3, 100)))
                      .tolist(), max_new_tokens=int(rng.integers(2, 12)))
        eng.step()
        counters = eng.last_step_counters
        fused += counters["chunks"] > 0 and counters["decode_tokens"] > 0
        for u in eng.finished_uids() + [
                u for u in live if eng.state.get(u).budget_spent
                and not eng.state.get(u).in_flight]:
            if u in live:
                eng.flush(u)
                live.remove(u)
    assert fused >= 5
    assert compiles_total() == warmed
