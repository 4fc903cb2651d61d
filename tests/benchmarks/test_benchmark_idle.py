"""Whose idle is it: the readers of ``serve/idle``, ``host/gc`` and of
``ahead`` / ``starved`` (``harness/idle_readers.py``) on hand-built events and
gaps, the eight metrics built on them on a hand-built run, and where
``BENCHMARK.json`` lists them."""

import json
import pathlib

import pytest

from benchmarks.harness import idle_readers as ir
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness.cells import find_cell, load_module
from benchmarks.harness.observations import Observations

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
LOOP, GC_TRACK, REQUEST = 7, 900_001, 1_000_001
READERS = ("idle_with_work_share", "step_starved_share", "step_ahead_share",
           "host_gc_pause_max_ms")
THROUGHPUT = ["mixtral8x7b-serve-batch-rag", "joyai-flash-serve-docqa",
              "laguna-s-serve-code-mixed", "xing4-serve-reasoning"]
LATENCY = ["mistral7b-serve-chat"]


def metric(name):
    return load_module(REPO, BENCH, "metrics", name)


def idle(t0, t1, **args):
    return pe.Event(ir.IDLE, t0, t1 - t0, LOOP,
                    dict({"polls": 3, "queued": 0, "inflight": 0,
                          "woke": "timeout"}, **args))


def step(n, t0, ahead, starved=None, batch=4, chunk_only=False):
    """The spans of tick ``n`` that say how its step was dispatched: a decode
    with a batch, or (``chunk_only``) two chunks and no decode."""
    said = {"tick": n, "ahead": ahead}
    if starved is not None:
        said["starved"] = starved
    out = [pe.Event("serve/tick", t0, 0.01, LOOP, {"tick": n})]
    if chunk_only:
        out += [pe.Event(pe.PREFILL_CHUNK, t0 + 0.001, 0.002, LOOP,
                         dict(said, tokens=8, bucket=8, start=0)),
                pe.Event(pe.PREFILL_CHUNK, t0 + 0.003, 0.002, LOOP,
                         dict(said, tokens=8, bucket=8, start=8))]
    else:
        out.append(pe.Event(pe.STEP_DECODE, t0 + 0.001, 0.008, LOOP,
                            dict(said, batch=batch, bucket=8)))
    return out


# --- idle with work ------------------------------------------------------------

def test_a_gap_inside_serve_idle_is_the_traffics_one_outside_the_programs():
    evs = [idle(10.0, 10.25), idle(10.25, 10.5), idle(10.5, 10.6),
           pe.Event("serve/tick", 10.6, 0.05, LOOP, {"tick": 1}),
           # a request's track holds every instant and is no answer
           pe.Event("serve/decode", 9.0, 3.0, REQUEST, {"uid": 1})]
    inside = (10.05, 10.20)           # 150 ms in the first piece
    straddling = (10.20, 10.30)       # 100 ms over the cut at 10.25
    on_the_cut = (10.45, 10.55)       # its midpoint is the cut at 10.5 itself
    outside = (10.61, 10.64)          # 30 ms inside the tick
    after = (10.70, 10.72)            # 20 ms where nothing is stamped
    gaps = [inside, straddling, on_the_cut, outside, after]
    assert ir.idle_with_work_seconds(gaps, evs) == pytest.approx(0.05)
    assert ir.idle_with_work_seconds([inside, straddling, on_the_cut],
                                     evs) == 0.0
    assert ir.idle_with_work_seconds([], evs) == 0.0
    # a program that stamps no serve/idle answers for all of it
    assert ir.idle_with_work_seconds(gaps, evs[3:]) == \
        pytest.approx(sum(b - a for a, b in gaps))


def test_queued_or_inflight_on_the_span_do_not_change_whose_idle_it_is():
    """``idle with requests waiting`` is read off the span's args by whoever
    looks at the trace; the share counts a wait as a wait."""
    evs = [idle(5.0, 5.2, queued=3, inflight=2)]
    assert ir.idle_with_work_seconds([(5.05, 5.15)], evs) == 0.0


# --- ahead and starved -----------------------------------------------------------

def test_one_span_a_dispatching_tick_says_ahead_and_starved():
    evs = (step(1, 1.00, ahead=0)
           + step(2, 1.01, ahead=1, starved=1)
           + step(3, 1.02, ahead=1, starved=0)
           + step(4, 1.03, ahead=1, starved=0, chunk_only=True)
           + step(5, 1.04, ahead=1)                  # could not be told
           # a tick that only collected the last step: no batch, no say
           + [pe.Event(pe.STEP_DECODE, 1.05, 0.004, LOOP,
                       {"tick": 6, "ahead": 0, "rows_dropped": 0}),
              idle(1.06, 1.2)])
    steps = ir.step_spans(evs)
    assert [e.arg("tick") for e in steps] == [1, 2, 3, 4, 5]
    # of a tick of chunks alone, the last chunk speaks
    assert steps[3].name == pe.PREFILL_CHUNK and steps[3].arg("start") == 8
    assert ir.ahead_share(steps) == pytest.approx(100 * 4 / 5)
    assert ir.starved_share(steps) == pytest.approx(100 * 1 / 3)


def test_no_step_dispatched_ahead_gives_nothing_to_read():
    depth_0 = step(1, 1.0, ahead=0) + step(2, 1.01, ahead=0)
    assert ir.ahead_share(ir.step_spans(depth_0)) == 0.0
    assert ir.starved_share(ir.step_spans(depth_0)) is None
    # an engine that says ``ahead`` and not ``starved``: the parent's
    older = step(1, 1.0, ahead=0) + step(2, 1.01, ahead=1)
    assert ir.ahead_share(ir.step_spans(older)) == 50.0
    assert ir.starved_share(ir.step_spans(older)) is None
    assert ir.ahead_share([]) is None and ir.starved_share([]) is None
    assert ir.step_spans([idle(1.0, 1.2)]) == []


# --- the interpreter's pauses ----------------------------------------------------

def test_the_longest_collection_on_any_thread():
    evs = [pe.Event(ir.GC, 2.0, 0.0004, GC_TRACK, {"generation": 1}),
           pe.Event(ir.GC, 2.5, 0.1153, GC_TRACK, {"generation": 2}),
           pe.Event("serve/tick", 2.4, 0.3, LOOP, {"tick": 1})]
    assert ir.gc_pause_max_ms(evs) == pytest.approx(115.3)
    assert ir.gc_pause_max_ms(evs[2:]) == 0.0


# --- the eight metrics on a hand-built run -----------------------------------------

@pytest.fixture
def run(monkeypatch):
    """A window of 2 s with a traced second in its middle: the device works
    for 100 ms of every 250, the loop waits for work from 50.55 to 50.95."""
    obs = Observations(kind="serve", cell=None, devices=[])
    obs.window = (50.0, 52.0)
    obs.host_exclude = [(50.4, 50.5)]
    ops = [tr.Op(f"fusion.{i}", 50.5 + 0.25 * i, 0.1, 0, "jit_decode_step_g")
           for i in range(4)]
    obs.trace = tr.Trace(ops, [], [0], window=(50.5, 51.5))
    ring = (step(1, 50.10, ahead=0)
            + step(2, 50.45, ahead=1, starved=1)     # in the profiler's start
            + step(3, 50.52, ahead=1, starved=1)
            + [idle(50.55, 50.80), idle(50.80, 50.95)]
            + step(4, 50.96, ahead=0)
            + step(5, 51.10, ahead=1, starved=0)
            + step(6, 51.30, ahead=1, starved=0)
            + step(7, 52.50, ahead=1, starved=1)     # after the window
            + [pe.Event(ir.GC, 51.2, 0.004, GC_TRACK, {"generation": 2}),
               pe.Event(ir.GC, 49.0, 0.2, GC_TRACK, {"generation": 2})])
    monkeypatch.setattr(pe, "events", lambda: ring)
    return obs


def test_the_metrics_read_the_run_and_each_twin_reads_the_same(run):
    got = {name: metric(name).read(run) for name in READERS}
    for name in READERS:
        assert metric("tick_" + name).read(run) == got[name]
    # idle gaps of the traced second: 50.6-50.75 and 50.85-50.95 (the first
    # two lie in serve/idle, 250 ms), 51.1-51.25 and 51.35-51.5 (300 ms in
    # no idle span)
    assert got["idle_with_work_share"] == pytest.approx(30.0)
    # ticks 1, 3, 4, 5, 6 lie whole in the window and outside the stall
    assert got["step_ahead_share"] == pytest.approx(100 * 3 / 5)
    assert got["step_starved_share"] == pytest.approx(100 * 1 / 3)
    assert got["host_gc_pause_max_ms"] == pytest.approx(4.0)


def test_a_run_without_a_devices_plane_reports_nothing(run):
    """On the CPU the profiler's trace has no device plane: whatever the
    spans say, no number is written under these names."""
    run.trace = tr.Trace([], [], [], window=(50.5, 51.5))
    for name in READERS:
        assert metric(name).read(run) is None
        assert metric("tick_" + name).read(run) is None
    run.trace = None
    assert all(metric(name).read(run) is None for name in READERS)


def test_a_program_without_the_spans_reports_nothing(run, monkeypatch):
    """The parent's side of a comparison: its registry of trace names has
    neither span, and its steps say ``ahead`` alone."""
    monkeypatch.setattr(ir, "program_has", lambda name: False)
    older = [pe.Event(e.name, e.start, e.dur, e.tid,
                      {k: v for k, v in (e.args or {}).items()
                       if k != "starved"})
             for e in pe.events() if e.name not in (ir.IDLE, ir.GC)]
    monkeypatch.setattr(pe, "events", lambda: older)
    assert metric("idle_with_work_share").read(run) is None
    assert metric("host_gc_pause_max_ms").read(run) is None
    assert metric("step_starved_share").read(run) is None
    assert metric("step_ahead_share").read(run) == pytest.approx(60.0)


def test_the_program_under_test_declares_both_spans():
    assert ir.program_has(ir.IDLE) and ir.program_has(ir.GC)
    assert not ir.program_has("serve/no_such_span")


# --- where BENCHMARK.json lists them ---------------------------------------------

@pytest.mark.parametrize("name", READERS)
def test_each_reader_is_listed_twice_by_what_it_moves(name, bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    plain, twin = by_name[name], by_name["tick_" + name]
    assert plain["moves"] == "serve_tokens_per_s"
    assert twin["moves"] == "serve_tpot_p50_ms"
    assert set(THROUGHPUT) <= set(plain["workloads"])
    assert set(LATENCY) <= set(twin["workloads"])
    # a training cell has no serve loop to idle: every cell listed is served
    for cell in plain["workloads"] + twin["workloads"]:
        assert find_cell(bench, cell, REPO).traffic["kind"] == "serve"
    for key in ("unit", "better", "source", "layer"):
        assert plain[key] == twin[key]
    assert plain["layer"] in {m["layer"] for m in bench["per_layer"]
                              if not m["name"].endswith(name)}
