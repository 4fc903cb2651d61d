"""The four decode rooflines that take a kernel's device time and the bytes
from the same stretches (``paged_kernel_roofline``, ``paged_decode_roofline``,
``latent_paged_roofline``, ``mixed_paged_roofline``), on hand-built rings and
operation lists, with a step in flight (the shape ``tests/test_step_in_flight.py``
holds the program's own ring to) and without one. Since PR 38 loop k
dispatches step k and then waits for step k-1, so a step's kernels run while
the host is anywhere in its loop: what holds them is the interval between the
ends of two waits (``costs_latent.ticks_with_device_work``), not a host span
(``serve/step_decode``, or the benchmark's own tick round ``engine.step``)."""

import json
import types

import pytest

import benchmark_rehearsal as rehearsal
from benchmarks.harness import cells, costs_latent
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn

REPO = rehearsal.REPO
PATHS = {"paths": ["benchmarks"]}
MS = 1e-3
TICK = 10 * MS
MOSAIC = 'custom-call(), custom_call_target="tpu_custom_call"'


def config(name):
    return json.loads((REPO / "benchmarks" / "configs" /
                       f"{name}.json").read_text())


#: reader -> (configuration, the kernel's name, least bytes of one decode
#: step whose sequences hold ``ctx`` tokens, ``ctx // 4`` behind a window)
CASES = {
    "paged_kernel_roofline": (
        config("mistral-7b-serve-d8"), "paged_attention",
        lambda ctx: 8 * 2 * (ctx // 4) * 8 * 128 * 2),
    "paged_decode_roofline": (
        config("mistral-7b-serve-d8"), "paged_attention",
        # two sequences of ctx / 2 tokens, each cut to the window of 4,096
        lambda ctx: 8 * 2 * 2 * min(ctx // 2, 4096) * 8 * 128 * 2),
    "latent_paged_roofline": (
        config("joyai-llm-flash-serve-d5"), "latent_paged_attention",
        lambda ctx: 5 * ctx * (512 + 64) * 2),
    "mixed_paged_roofline": (
        config("laguna-s-2.1-serve-d5-e128"), "paged_attention",
        lambda ctx: (2 * ctx + 3 * (ctx // 4)) * 2 * 8 * 128 * 2),
}


def ctx_of(step):
    return 6000 + 1000 * step


def loop(n, depth):
    """(ring, [(t0, t1, counters, contexts)] as ``_watch_ticks`` notes them,
    {step: (device start, device end)}). Loop k starts at ``k * TICK``, builds
    and dispatches step k (1 to 3 ms), waits (to 6.9 ms) and commits (to 7
    ms): one ``serve/step_decode`` over all of it. At depth 1 the wait is for
    step k-1 (loop 1 waits for nothing, and a last loop dispatches nothing
    and collects step n); at depth 0 for step k itself. The device is never
    dry: a step ends where its wait does and the next starts there."""
    ring, watched, device = [], [], {}
    for k in range(1, n + 1 + depth):
        t0, a = k * TICK, {"tick": k}
        dispatches, waits = k <= n, not (depth and k == 1)
        if dispatches:
            ring += [pe.Event("serve/decode_build", t0 + 1 * MS, 1 * MS, 7, a),
                     pe.Event("serve/decode_dispatch", t0 + 2 * MS, 1 * MS,
                              7, a)]
        if waits:
            ring.append(pe.Event(pe.DECODE_WAIT, t0 + 3 * MS, 3.9 * MS, 7, a))
        counts = dict(a, batch=2, bucket=4, ctx_tokens=ctx_of(k),
                      ctx_tokens_windowed=ctx_of(k) // 4,
                      ahead=int(depth and k > 1)) if dispatches \
            else dict(a, ahead=0)
        ring.append(pe.Event(pe.STEP_DECODE, t0 + 1 * MS, 6 * MS, 7, counts))
        watched.append((t0 + 0.5 * MS, t0 + 7.5 * MS, {},
                        [ctx_of(k) // 2] * 2 if dispatches else []))
        if dispatches:
            start = t0 + 3 * MS if k == 1 or not depth else t0 + 6.9 * MS
            device[k] = (start, t0 + 6.9 * MS + depth * TICK)
    return ring, watched, device


def calls(device, kernel, places):
    """Four calls of ``kernel`` a step, 0.2, 0.3, 0.4 and 0.5 ms long, each
    starting ``places[i]`` ms after its step's start on the device, and one
    call of a chunk program and one of another kernel beside them."""
    out = []
    for start, _ in device.values():
        out += [xn.NamedOp(f"{kernel}.{i}", start + at * MS, dur * MS, 0,
                           "jit_decode_step_g", MOSAIC, "custom-call")
                for i, (at, dur) in enumerate(zip(places,
                                                  (0.2, 0.3, 0.4, 0.5)))]
        out += [xn.NamedOp(f"{kernel}.9", start + 1 * MS, 2 * MS, 0,
                           "jit_prefill_chunk_g", MOSAIC, "custom-call"),
                xn.NamedOp("fusion.3", start + 1 * MS, 2 * MS, 0,
                           "jit_decode_step_g", "", "fusion")]
    return out


def reading(monkeypatch, name, ring, watched, ops):
    reader = cells.load_module(REPO, PATHS, "metrics", name)
    window = (0.0, 100.0)
    obs = types.SimpleNamespace(
        device_kind="TPU v5 lite", ticks=watched,
        cell=types.SimpleNamespace(config=CASES[name][0]),
        trace=tr.Trace([tr.Op(o.name, o.start, o.dur, 0, o.program, o.detail,
                              o.kind) for o in ops], [], [0], window=window))
    monkeypatch.setattr(pe, "events", lambda: ring)
    if hasattr(reader, "named_readers"):
        monkeypatch.setattr(reader.named_readers, "named_ops",
                            lambda obs: (ops, [0], 0.0))
    return reader.read(obs)


#: where a step's four calls lie in its 10 ms on the device. With a step in
#: flight the step runs from 6.9 ms of the loop that dispatched it to 6.9 ms
#: of the next, whose ``serve/step_decode`` opens 4.1 ms in: ``inside`` puts
#: every call in that span; ``outside`` before it opens (the host is fanning
#: out, reaping, admitting), astride its opening, inside it, and astride the
#: next wait's end, where the call ends 0.1 ms into the next interval
INSIDE = (4.3, 5.3, 6.3, 7.3)
OUTSIDE = (0.5, 3.9, 6.5, 9.6)


@pytest.mark.parametrize("name", sorted(CASES))
def test_step_in_flight_reads_the_same_inside_and_outside_the_host_span(
        monkeypatch, name):
    """Six steps at depth 1. The ticks that ended in a wait are loops 2 to 7
    (loop 7 only collects); their stretch runs from loop 2's build at 21 ms
    to loop 7's wait at 76.9 ms, and the bytes beside it are the spans' of
    loops 2 to 6. INSIDE, the stretch holds the calls of steps 2 to 6 whole
    (step 1's end by 20.8 ms). OUTSIDE, the last call of every step lies
    astride the end of a wait and counts where it ends: step 1's (ends at
    23.1 ms) in loop 2's interval, step 6's (77.0 ms) past the last wait, so
    the same 0.5 ms in and out. Either way 5 x 1.4 ms. The span cut, which
    set the bytes of six spans against the calls it found whole inside a
    dispatching loop's span, found 4 x 1.4 ms INSIDE (step 6 runs under loop
    7's span, which dispatched nothing) and less OUTSIDE."""
    _, kernel, least = CASES[name]
    ring, watched, device = loop(6, depth=1)
    need = sum(least(ctx_of(k)) for k in range(2, 7))
    by_hand = 100 * need / 819e9 / (5 * 1.4 * MS)
    for places in (INSIDE, OUTSIDE):
        ops = calls(device, kernel, places)
        assert reading(monkeypatch, name, ring, watched, ops) == \
            pytest.approx(by_hand), places
    spans = tr.held_whole((e.start, e.end) for e in ring
                          if e.name == pe.STEP_DECODE and e.arg("batch"))
    in_spans = {places: sum(
        o.dur for o in calls(device, kernel, places)
        if o.kind == "custom-call" and "decode_step" in o.program
        and spans(o.start, o.end)) for places in (INSIDE, OUTSIDE)}
    assert in_spans[INSIDE] == pytest.approx(4 * 1.4 * MS)
    assert in_spans[OUTSIDE] < 0.8 * in_spans[INSIDE]


@pytest.mark.parametrize("name", sorted(CASES))
def test_without_a_step_in_flight_reads_what_the_span_cut_read(monkeypatch,
                                                               name):
    """Depth 0: a step runs inside its own loop's span and wait, so the
    interval cut and the span cut the parent made hold the same calls."""
    _, kernel, least = CASES[name]
    ring, watched, device = loop(4, depth=0)
    ops = calls(device, kernel, (0.5, 1.5, 2.5, 3.3))
    spans = [e for e in ring if e.name == pe.STEP_DECODE]
    in_a_span = tr.held_whole((e.start, e.end) for e in spans)
    spent = sum(o.dur for o in ops if o.name.startswith(kernel + ".")
                and o.name[-1] != "9" and in_a_span(o.start, o.end))
    assert spent == pytest.approx(4 * 1.4 * MS)
    need = sum(least(ctx_of(k)) for k in range(1, 5))
    assert reading(monkeypatch, name, ring, watched, ops) == pytest.approx(
        100 * need / 819e9 / spent)


def test_a_call_astride_two_intervals_counts_once_where_it_ends():
    ring, _, _ = loop(3, depth=1)
    found = costs_latent.ticks_with_device_work(ring, (0.0, 100.0))
    assert [work[0].arg("tick") for _, work in found] == [2, 3, 4]
    (lo, _), (_, hi) = found[0][0], found[-1][0]
    assert (lo, hi) == (pytest.approx(21 * MS), pytest.approx(46.9 * MS))
    ends = costs_latent.ends_in(found)
    edge = found[0][0][1]                       # loop 2's wait ends: 26.9 ms
    assert ends(edge - 0.1 * MS) and ends(edge) and ends(edge + 0.1 * MS)
    assert not ends(lo) and ends(lo + 1e-9)     # lo < end <= hi
    assert ends(hi) and not ends(hi + 1e-9)
    assert not costs_latent.ends_in([])(1.0)
    # the spans whose bytes stand beside those calls: the dispatching loops'
    assert [e.arg("tick") for e in costs_latent.decode_steps(
        found, "ctx_tokens")] == [2, 3]
    assert len(costs_latent.decode_steps(found)) == 3


@pytest.mark.parametrize("name", sorted(CASES))
def test_nothing_to_read_gives_nothing(monkeypatch, name):
    _, kernel, _ = CASES[name]
    ring, watched, device = loop(3, depth=1)
    ops = calls(device, kernel, INSIDE)
    other = [o for o in ops if not o.name.startswith(kernel + ".")
             or o.name[-1] == "9"]
    assert reading(monkeypatch, name, ring, watched, other) is None
    bare = [e for e in ring if e.name != pe.DECODE_WAIT]    # no wait named
    assert reading(monkeypatch, name, bare, watched, ops) is None
    assert reading(monkeypatch, name, [], watched, ops) is None
