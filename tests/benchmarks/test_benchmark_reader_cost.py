"""What reading a traced run costs (PR 27). A reader runs after the window and
inside the run's time limit, so one whose cost is gaps x events or calls x
ticks refuses the very change that shortens the tick (PR 26: 600 s in
``span_at``). Held here: the sweep (``trace.spans_at``) and the bisection
(``trace.held_whole``) answer as the plain scans they replaced, which are
copied below as the oracle, and a run of PR 26's shape is read in seconds on
this CPU."""

import json
import random
import time
import types

import pytest

from benchmarks.harness import program_events as pe, result
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn
from benchmarks.harness.observations import Observations
from test_benchmark_named import (LOOP, REPO, REQUEST, metric,  # noqa: F401
                                  recorded)


# --- the oracle: the scans as they stood before PR 27 ---------------------------

def span_at_by_scan(spans, t):
    holding = [s for s in spans if s.start <= t <= s.end]
    return min(holding, key=lambda s: s.dur).name if holding else "(no span)"


def attributed_idle_share_by_scan(gaps, evs):
    whole = sum(b - a for a, b in gaps)
    if not whole or not evs:
        return None
    named = sum(b - a for a, b in gaps
                if span_at_by_scan(evs, (a + b) / 2) not in pe.CONTAINERS)
    return 100.0 * named / whole


def held_whole_by_scan(intervals, a, b):
    return any(s <= a and b <= e for s, e in intervals)


# --- seeded cases ---------------------------------------------------------------

NAMES = ("serve/plan", "serve/admit", "serve/decode_wait", "serve/bookkeep",
         "serve/decode_dispatch") + pe.CONTAINERS[:4]


def random_case(seed):
    """(spans, gaps) about a window from 4 s to 6 s. Even seeds draw every time from a grid of
    1/1024 s, which a float holds exactly: spans of equal length, spans that
    share a start or an end, and midpoints that fall exactly on a span's
    start or end are then common. Odd seeds draw plain floats. Either way:
    spans nested three deep with children that overhang their parent, spans
    before and after the window, gaps before the first and after the last
    span, and a few spans of no length."""
    rng = random.Random(seed)
    grid = seed % 2 == 0

    def at(lo, hi):
        return rng.randrange(int(lo * 1024), int(hi * 1024) + 1) / 1024 \
            if grid else rng.uniform(lo, hi)

    window = (4.0, 6.0)
    spans = []

    def nest(lo, hi, depth):
        start = at(lo, hi)
        dur = at(0.0, max(hi - start, 0.0) * rng.choice((0.5, 1.0, 1.1)))
        spans.append(pe.Event(rng.choice(NAMES), start, dur, LOOP))
        for _ in range(rng.randrange(0, 4) if depth < 3 else 0):
            nest(start, start + dur, depth + 1)

    for _ in range(rng.randrange(1, 12)):
        nest(rng.choice((0.0, 3.5, 4.0)), rng.choice((6.0, 6.5, 10.0)), 0)
    for _ in range(rng.randrange(0, 3)):        # twins: equally short
        twin = rng.choice(spans)
        spans.insert(rng.randrange(len(spans) + 1),
                     pe.Event(rng.choice(NAMES), twin.start, twin.dur, LOOP))
    rng.shuffle(spans)
    gaps = []
    for _ in range(rng.randrange(1, 40)):
        a = at(*window)
        gaps.append((a, a + at(0.0, 0.25)))
    for s in rng.sample(spans, min(4, len(spans))):
        half = at(0.0, 0.125)
        gaps.append((s.start - half, s.start + half))   # midpoint on a start
        gaps.append((s.end - half, s.end + half))       # and on an end
    first = min(s.start for s in spans)
    last = max(s.end for s in spans)
    gaps += [(first - 1.0, first - 0.5), (last + 0.5, last + 1.0)]
    rng.shuffle(gaps)
    return spans, gaps


@pytest.mark.parametrize("seed", range(200))
def test_sweep_names_and_share_equal_the_scan(seed):
    spans, gaps = random_case(seed)
    mids = [(a + b) / 2 for a, b in gaps]
    assert tr.spans_at(spans, mids) == [span_at_by_scan(spans, t) for t in mids]
    assert [tr.span_at(spans, t) for t in mids] == \
        [span_at_by_scan(spans, t) for t in mids]
    got = pe.attributed_idle_share(gaps, spans)
    want = attributed_idle_share_by_scan(gaps, spans)
    assert got == want and type(got) is type(want)       # the same float
    # the ten longest gaps are named the same way
    trace = tr.Trace([], list(spans), [0])
    longest = tr.longest_gaps(trace, (3.0, 7.0), 0)
    assert longest == [(span_at_by_scan(spans, 5.0), 4.0)]


def test_sweep_on_the_edges():
    spans = [tr.Span("a", 1.0, 1.0), tr.Span("b", 1.0, 1.0),
             tr.Span("c", 2.0, 0.0), tr.Span("d", 5.0, -1.0)]
    assert tr.spans_at(spans, []) == []
    assert tr.spans_at([], [1.0, 2.0]) == ["(no span)", "(no span)"]
    # equally short: the first in the list; ends inclusive; no length holds
    # its own instant; a span that ends before it starts holds nothing
    assert tr.spans_at(spans, [2.0, 1.0, 1.5, 4.5, 0.5, 2.5, 1.5, 5.0]) == \
        ["c", "a", "a", "(no span)", "(no span)", "(no span)", "a",
         "(no span)"]
    assert tr.spans_at(spans[1:], [1.5]) == ["b"]


@pytest.mark.parametrize("seed", range(50))
def test_bisection_answers_as_the_scan_over_overlapping_ticks(seed):
    rng = random.Random(seed)

    def at():
        return rng.randrange(0, 257) / 256 if seed % 2 == 0 else rng.random()

    ticks = [(s, s + rng.choice((0.0, at() / 8))) for s in
             (at() for _ in range(rng.randrange(0, 30)))]
    held = tr.held_whole(iter(ticks))
    calls = [(a, a + at() / 16) for a in (at() for _ in range(200))]
    calls += [(s, e) for s, e in ticks] + [(s, s) for s, _ in ticks]
    assert [held(a, b) for a, b in calls] == \
        [held_whole_by_scan(ticks, a, b) for a, b in calls]


# --- the trace recorded on the chip (test_benchmark_named.py), read both ways ----

def test_recorded_chip_trace_reads_the_same_share(recorded):
    _, ring, trace = recorded
    gaps = tr.idle_gaps(trace, trace.window, 0)
    loop = pe.loop_thread(ring)
    assert len(gaps) > 100
    assert pe.attributed_idle_share(gaps, loop) == \
        attributed_idle_share_by_scan(gaps, loop)
    mids = [(a + b) / 2 for a, b in gaps]
    assert tr.spans_at(ring, mids) == [span_at_by_scan(ring, t) for t in mids]


# --- PR 26's shape --------------------------------------------------------------

TICK_S = 0.0085
PHASES = ("serve/drain_expire", "serve/admit", "serve/plan",
          "serve/decode_build", "serve/decode_dispatch", "serve/decode_wait",
          "serve/decode_commit", "serve/step_finish", "serve/drain_fanout",
          "serve/drain_reap", "serve/bookkeep", "serve/metrics")


def fast_tick(n, t0):
    """One decode tick of 8.5 ms as the program emits it: twelve leaf phases
    end to end over the first 8 ms, and the four spans that hold them."""
    a = {"tick": n}
    step = 0.008 / len(PHASES)
    out = [pe.Event(name, t0 + i * step, step, LOOP, a)
           for i, name in enumerate(PHASES)]
    out += [pe.Event("serve/step_decode", t0 + 3 * step, 5 * step, LOOP,
                     dict(a, batch=2, bucket=4, ctx_tokens=2400,
                          ctx_tokens_windowed=2400, ctx_blocks=32)),
            pe.Event("serve/engine_step", t0 + 2 * step, 6 * step, LOOP, a),
            pe.Event("serve/step_prefill", t0 + 2 * step, 0.0, LOOP, a),
            pe.Event("serve/tick", t0, TICK_S, LOOP, a)]
    return out


@pytest.fixture(scope="module")
def fast_run():
    """6,000 ticks of 8.5 ms (51 s: ramp, window, drain) with sixteen spans
    each and the requests' own tracks, 102,000 events; a traced window of 3 s
    in the middle in which the device runs 100,000 operations of 10 us, 20 us
    apart, eight of them paged kernel calls in each tick."""
    t_first = 100.0
    ring = []
    for n in range(6000):
        ring += fast_tick(n, t_first + n * TICK_S)
    ring += [pe.Event("serve/decode", t_first + i, 30.0, REQUEST + i,
                      {"uid": i}) for i in range(20)]
    window = (t_first + 24.0, t_first + 27.0)
    kernel = ('%paged_attention.5 = bf16[8,128]{1,0} custom-call(bf16[8] %q), '
              'custom_call_target="tpu_custom_call"')
    ops = []
    for i in range(100_000):
        start = window[0] + 1e-5 + i * 3e-5
        paged = i % 35 == 0
        ops.append(xn.NamedOp(
            "paged_attention.5" if paged else f"fusion.{i % 50}", start, 1e-5,
            0, "jit_decode_step_g", kernel if paged else "",
            "custom-call" if paged else "fusion"))
    names = xn.Names(ops, [tr.Span("bench/clock_sync", window[0], 0.0005)], [0])
    trace = tr.Trace([tr.Op(o.name, o.start, o.dur, 0, o.program, o.detail,
                            o.kind) for o in ops],
                     [tr.Span("clock_sync", window[0], 0.0005)], [0],
                     window=window)
    cell = types.SimpleNamespace(
        name="c", root=REPO, config={
            "num_hidden_layers": 8, "num_key_value_heads": 8,
            "num_attention_heads": 32, "hidden_size": 4096,
            "torch_dtype": "bfloat16", "sliding_window": 4096})
    obs = Observations(kind="serve", cell=cell, window=(116.0, 167.0),
                       devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
                       trace=trace)
    obs.ticks = [(e.start, e.end, {}, [1200, 1200]) for e in ring
                 if e.name == pe.STEP_DECODE]
    return obs, ring, names


@pytest.fixture
def fast(fast_run, monkeypatch):
    obs, ring, names = fast_run
    monkeypatch.setattr(pe, "events", lambda: ring)
    monkeypatch.setattr(xn, "of_run", lambda o: names)
    return obs


def test_a_run_of_pr26s_shape_is_read_in_seconds(fast, fast_run):
    _, ring, _ = fast_run
    gaps = tr.idle_gaps(fast.trace, fast.trace.window, 0)
    assert len(gaps) >= 100_000 and len(pe.loop_thread(ring)) >= 96_000
    t0 = time.monotonic()
    share = metric("tick_idle_attributed_share").read(fast)
    took = time.monotonic() - t0
    assert took < 5.0, f"{took:.1f} s: gaps x events again?"
    # one tick's gaps against one tick's events, by the scan: every tick of
    # the window has the same share but for the window's two ends
    a = fast.trace.window[0] + 100 * TICK_S
    some = [g for g in gaps if a <= g[0] < a + 10 * TICK_S]
    near = [e for e in ring if a - 1.0 <= e.start <= a + 1.0]
    assert share == pytest.approx(attributed_idle_share_by_scan(some, near),
                                  rel=0.01)
    assert 90.0 < share < 100.0


def test_the_rooflines_of_a_long_window_are_read_in_seconds(fast, fast_run,
                                                            monkeypatch):
    """Every tick of the run inside the traced window: 6,000 ticks x 48,000
    kernel calls, which the scan tests pair by pair."""
    _, ring, names = fast_run
    ticks = [e for e in ring if e.name == pe.STEP_DECODE]
    k = next(o for o in names.ops if o.detail)

    def call(start):
        return xn.NamedOp(k.name, start, 5e-5, 0, k.program, k.detail, k.kind)
    calls = [call(t.start + (i + 1) * 1e-4) for t in ticks for i in range(8)]
    # one call a tick hangs over its span's end: it ends after the tick's
    # wait, in the next tick's interval, and counts there in both readers;
    # the last tick's ends in no interval
    late = [call(t.end - 1e-5) for t in ticks]
    window = (ring[0].start - 1.0, ticks[-1].end + 1.0)
    monkeypatch.setattr(xn, "of_run", lambda o: xn.Names(
        calls + late, names.host, [0]))
    sync = [tr.Span("clock_sync", fast.trace.window[0], 0.0005)]
    monkeypatch.setattr(fast, "trace", tr.Trace(
        [tr.Op(o.name, o.start, o.dur, 0, o.program, o.detail, o.kind)
         for o in calls + late], sync, [0], window=window))
    t0 = time.monotonic()
    by_name = metric("paged_kernel_roofline").read(fast)
    by_shape = metric("paged_decode_roofline").read(fast)
    took = time.monotonic() - t0
    assert took < 5.0, f"{took:.1f} s: calls x ticks again?"
    need = 8 * (2 * 2400 * 8 * 128 * 2) * len(ticks)   # layers x K, V x bf16
    expected = 100 * (need / 819e9) / ((len(calls) + len(late) - 1) * 5e-5)
    assert by_name == pytest.approx(expected)
    assert by_shape == pytest.approx(expected)


# --- the run says what reading cost -----------------------------------------------

def test_a_slow_reader_and_the_time_after_the_window_are_said(tmp_path,
                                                               monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "slow.py").write_text(
        "import time\ndef read(obs):\n    time.sleep(0.06)\n    return 1.0\n")
    (tmp_path / "metrics" / "quick.py").write_text(
        "def read(obs):\n    return 2.0\n")
    cell = types.SimpleNamespace(
        root=tmp_path.parent, end_to_end=[],
        per_layer=[{"name": "slow", "unit": "s"}, {"name": "quick", "unit": "s"}])
    obs = Observations(kind="serve", cell=cell, devices=[],
                       window=(0.0, time.monotonic() - 7.0))
    monkeypatch.setattr(result, "SLOW_READER_S", 0.05)
    monkeypatch.setattr(result, "device_object", lambda obs, traced: {})
    lines = []
    line = result.result_line(cell, {"paths": [tmp_path.name]}, obs, True,
                              lines.append)
    assert set(json.loads(line)["metrics"]) == {"slow", "quick"}
    slow, last = lines
    assert slow == "metric slow: read in 0.1 s"
    assert last.startswith("from the window's close to the result line 7.")
    assert "of which reading the metrics 0.1 s" in last
