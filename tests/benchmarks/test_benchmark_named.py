"""The readers of what the program names: the tick's phases with their counts
(``harness/program_events.py``), kernel names and scopes from the
``.xplane.pb`` (``harness/xplane_names.py``), and the metrics built on them,
on hand-built events and a hand-encoded trace file."""

import gzip
import json
import pathlib
import types

import pytest

from benchmarks.harness import named_readers, program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn
from benchmarks.harness.cells import load_module
from benchmarks.harness.observations import Observations

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = {"paths": ["benchmarks"]}
LOOP, REQUEST = 7, 1_000_001


def metric(name):
    return load_module(REPO, BENCH, "metrics", name)


# --- a tick, as the program emits it -----------------------------------------

def tick(n, t0, wait_end, bubble_next=None, batch=10, bucket=16, ctx=4000,
         prefill=None):
    """One decode tick starting at ``t0`` (seconds): plan 0.2 ms, build 1 ms,
    dispatch 2 ms, the wait until ``wait_end``, commit 0.1 ms, then drain and
    bookkeeping; optionally a prefill chunk (tokens, bucket) before it."""
    a = {"tick": n}
    out = [pe.Event("serve/drain_expire", t0, 0.0001, LOOP, a),
           pe.Event("serve/admit", t0 + 0.0001, 0.0002, LOOP, a),
           pe.Event("serve/plan", t0 + 0.0004, 0.0002, LOOP, a)]
    t = t0 + 0.0006
    if prefill is not None:
        tokens, padded = prefill
        out += [pe.Event("serve/prefill_chunk", t, 0.003, LOOP,
                         dict(a, tokens=tokens, bucket=padded, start=0)),
                pe.Event("serve/step_prefill", t, 0.003, LOOP, a)]
        t += 0.003
    out += [pe.Event("serve/decode_build", t, 0.001, LOOP, a),
            pe.Event("serve/decode_dispatch", t + 0.001, 0.002, LOOP, a),
            pe.Event("serve/decode_wait", t + 0.003, wait_end - t - 0.003,
                     LOOP, a),
            pe.Event("serve/decode_commit", wait_end, 0.0001, LOOP, a),
            pe.Event("serve/step_decode", t, wait_end + 0.0001 - t, LOOP,
                     dict(a, batch=batch, bucket=bucket, ctx_tokens=ctx,
                          ctx_tokens_windowed=ctx // 2, ctx_blocks=32)),
            pe.Event("serve/engine_step", t0 + 0.0004,
                     wait_end + 0.0002 - t0 - 0.0004, LOOP, a),
            pe.Event("serve/drain_fanout", wait_end + 0.0002, 0.0005, LOOP, a),
            pe.Event("serve/drain_reap", wait_end + 0.0007, 0.0001, LOOP, a),
            pe.Event("serve/bookkeep", wait_end + 0.0008, 0.0004, LOOP, a),
            pe.Event("serve/tick", t0, wait_end + 0.0013 - t0, LOOP, a)]
    return out


@pytest.fixture
def ticks():
    """Three ticks of 50 ms; the second carries a prefill chunk. A request's
    own track spans the whole time."""
    evs = (tick(1, 10.000, 10.048) + tick(2, 10.050, 10.098, prefill=(700, 1024),
                                          batch=12)
           + tick(3, 10.100, 10.148, batch=17, bucket=32))
    evs.append(pe.Event("serve/decode", 9.0, 3.0, REQUEST, {"uid": 1}))
    return evs


def test_loop_thread_leaves_request_tracks_out(ticks):
    loop = pe.loop_thread(ticks)
    assert {e.tid for e in loop} == {LOOP}
    assert len(loop) == len(ticks) - 1


def test_inside_keeps_whole_events_outside_the_stall(ticks):
    kept = pe.inside(ticks, (10.049, 10.2), keep=lambda t: not 10.12 < t < 10.13)
    assert {e.arg("tick") for e in kept} == {2, 3}
    # tick 3's containers straddle the stalled instant at neither end
    assert all(e.start >= 10.049 for e in kept)


def test_decode_bubble_runs_from_the_wait_to_the_next_dispatch(ticks):
    bubbles = pe.decode_bubbles(pe.loop_thread(ticks))
    # tick 1 -> 2: the wait ends at 10.048, tick 2 opens its prefill chunk
    # (the first dispatch span) at 10.0506; tick 2 -> 3: 10.098 to the
    # decode dispatch at 10.1016
    assert bubbles == pytest.approx([0.0026, 0.0036], abs=1e-9)


def test_bucket_fill_sums_before_it_divides(ticks):
    assert pe.fill(ticks, pe.STEP_DECODE, "batch", "bucket") == \
        pytest.approx(100 * (10 + 12 + 17) / (16 + 16 + 32))
    assert pe.fill(ticks, pe.PREFILL_CHUNK, "tokens", "bucket") == \
        pytest.approx(100 * 700 / 1024)
    # a program that counts nothing on its spans gives nothing to read
    bare = [pe.Event(pe.STEP_DECODE, 1.0, 0.05, LOOP, {"batch": 3})]
    assert pe.fill(bare, pe.STEP_DECODE, "batch", "bucket") is None
    assert pe.fill([], pe.STEP_DECODE, "batch", "bucket") is None


def test_idle_is_attributed_to_the_shortest_leaf_phase(ticks):
    loop = pe.loop_thread(ticks)
    gaps = [(10.0485, 10.0495),     # 1 ms: drain/bookkeep of tick 1 -> named
            (10.0512, 10.0522),     # 1 ms inside tick 2's prefill chunk
            (10.1490, 10.1530),     # 4 ms, midpoint after the last tick: none
            (10.09812, 10.09818)]   # 60 us between commit and fan-out: the
    #                                 shortest span there is engine_step
    assert tr.span_at(loop, 10.049) == "serve/bookkeep"
    assert tr.span_at(loop, 10.0517) == "serve/prefill_chunk"
    assert tr.span_at(loop, 10.151) == "(no span)"
    assert tr.span_at(loop, 10.09815) == "serve/engine_step"
    assert pe.attributed_idle_share(gaps, loop) == \
        pytest.approx(100 * 0.002 / 0.00606)
    assert pe.attributed_idle_share([], loop) is None
    assert pe.attributed_idle_share(gaps, []) is None


# --- an .xplane.pb, encoded by hand --------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def plane(name, lines, events, stats=()):
    """``events``: {metadata id: (name, {stat name: text})}; ``lines``:
    [(name, t0 ns, [(metadata id, offset ps, duration ps)])]."""
    stat_ids = {s: i + 1 for i, s in enumerate(stats)}
    out = field(2, name)
    for line_name, t0_ns, evs in lines:
        body = field(2, line_name) + field(3, t0_ns)
        for meta_id, offset, dur in evs:
            body += field(4, field(1, meta_id) + field(2, offset)
                          + field(3, dur))
        out += field(3, body)
    for meta_id, (ev_name, ev_stats) in events.items():
        meta = field(1, meta_id) + field(2, ev_name)
        for stat, text in ev_stats.items():
            meta += field(5, field(1, stat_ids[stat]) + field(5, text))
        out += field(4, field(1, meta_id) + field(2, meta))
    for stat, stat_id in stat_ids.items():
        out += field(5, field(1, stat_id)
                     + field(2, field(1, stat_id) + field(2, stat)))
    return field(1, out)


US = 1_000_000      # picoseconds


@pytest.fixture
def xplane(tmp_path):
    """One device: a decode program whose ``while`` encloses a paged kernel
    call, an expert matmul and a copy; a host thread with the sync mark and
    two mirrored spans."""
    hlo = {
        1: ("jit_decode_step_g(123)", {}),
        2: ("%while.1 = (s32[]) while(s32[] %a), body=%b", {}),
        3: ('%paged_attention.5 = bf16[8,128]{1,0} custom-call(bf16[8] %q), '
            'custom_call_target="tpu_custom_call"',
            {"tf_op": "jit(decode_step_g)/attn/paged/paged_attention/"
                      "pallas_call:"}),
        4: ("%fusion.9 = bf16[8,16]{1,0} fusion(bf16[8] %x), kind=kOutput",
            {"tf_op": "jit(decode_step_g)/moe/experts/dot_general:"}),
        5: ("%copy.2 = bf16[4]{0} copy(bf16[4] %p)", {}),
    }
    device = plane("/device:TPU:0", [
        ("XLA Modules", 1000, [(1, 0, 900 * US)]),
        ("XLA Ops", 1000, [(2, 10 * US, 800 * US), (3, 20 * US, 300 * US),
                           (4, 400 * US, 200 * US), (5, 850 * US, 40 * US)]),
    ], hlo, stats=("tf_op",))
    host = plane("/host:CPU", [
        ("python3", 0, [(1, 500_000, 500_000), (2, 5 * US, 100 * US),
                        (3, 200 * US, 300 * US)]),
    ], {1: ("bench/clock_sync", {}), 2: ("serve/decode_dispatch", {}),
        3: ("serve/decode_wait", {})})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + host)
    return str(path)


def test_wire_reader_gives_names_scopes_programs_and_leaves(xplane):
    names = xn.read(xplane)
    assert names.devices == [0]
    by_name = {o.name: o for o in names.ops}
    assert set(by_name) == {"paged_attention.5", "fusion.9", "copy.2"}
    kernel = by_name["paged_attention.5"]
    assert xn.kernel_of(kernel) == "paged_attention"
    assert kernel.scope.startswith("jit(decode_step_g)/attn/paged/")
    assert kernel.program == "jit_decode_step_g"
    assert kernel.start == pytest.approx(1e-6 + 20e-6)
    assert kernel.dur == pytest.approx(300e-6)
    assert xn.kernel_of(by_name["fusion.9"]) == ""
    assert "/moe/experts/" in by_name["fusion.9"].scope
    assert by_name["copy.2"].scope == "" and by_name["copy.2"].kind == "copy"
    assert {s.name for s in names.host} == {
        "bench/clock_sync", "serve/decode_dispatch", "serve/decode_wait"}


def test_same_events_as_the_benchmarks_own_reader():
    """Against ``trace.read_xplane`` on the trace the repository keeps: the
    same operations at the same times, so the two can be joined. jax's
    reader hands out whole nanoseconds and this one picoseconds, so next to
    an operation of one nanosecond the two may disagree on what encloses
    what: under 1% of the operations."""
    path = str(REPO / "tests" / "benchmarks" / "data" / "train-step.xplane.pb")
    mine, theirs = xn.read(path), tr.read_xplane(path)
    at = {}
    for o in mine.ops:
        at.setdefault((o.device, o.name), []).append(o)
    kept = [o for o in theirs.ops if not o.overlapped]
    for o in kept:
        twins = [m for m in at[o.device, o.name]
                 if abs(m.start - o.start) < 2e-9]
        assert twins and abs(twins[0].dur - o.dur) < 2e-9, o.name
        assert twins[0].program == o.program and twins[0].kind == o.kind
    assert len(kept) <= len(mine.ops) <= 1.01 * len(kept)
    marks = sorted(s.start for s in theirs.spans if s.name == "clock_sync")
    assert sorted(s.start for s in mine.host
                  if s.name == "bench/clock_sync") == \
        pytest.approx(marks, abs=2e-9)


def test_shift_and_skew_tie_the_two_clocks(xplane):
    names = xn.read(xplane)
    # the harness put the sync mark at monotonic 50.0 s
    trace = tr.Trace([], [tr.Span("clock_sync", 50.0, 0.0005)], [0],
                     window=(50.0, 50.001))
    shift = xn.shift_to_monotonic(names, trace)
    assert shift == pytest.approx(50.0 - 0.5e-6)
    ring = [pe.Event("serve/decode_dispatch", 50.0 + 4.5e-6 - 3e-6, 110e-6),
            pe.Event("serve/decode_wait", 50.0 + 199.5e-6 - 7e-6, 310e-6),
            pe.Event("serve/tick", 50.0, 0.0009)]      # not mirrored
    assert xn.clock_skew_s(names, ring, shift, trace.window) == \
        pytest.approx(7e-6, abs=1e-9)
    # a program whose spans are not mirrored: nothing to compare
    assert xn.clock_skew_s(names, ring[2:], shift, trace.window) is None
    assert xn.shift_to_monotonic(names, tr.Trace([], [], [0])) is None


# --- the metrics, on a hand-built run -------------------------------------------

@pytest.fixture
def run(xplane, monkeypatch):
    """A traced chat run made by hand: the file above, three ring events,
    and the published Mistral widths (8 KV heads x 128, bf16, 2 layers)."""
    cell = types.SimpleNamespace(
        name="c", root=REPO, config={
            "num_hidden_layers": 2, "num_key_value_heads": 8,
            "num_attention_heads": 32, "hidden_size": 4096,
            "torch_dtype": "bfloat16", "sliding_window": 4096})
    device = types.SimpleNamespace(device_kind="TPU v5 lite")
    names = xn.read(xplane)
    shift = 50.0 - 0.5e-6
    trace = tr.Trace(
        [tr.Op(o.name, o.start + shift, o.dur, 0, o.program, o.detail, o.kind)
         for o in names.ops],
        [tr.Span("clock_sync", 50.0, 0.0005)], [0], window=(50.0, 50.001))
    obs = Observations(kind="serve", cell=cell, devices=[device],
                       window=(49.0, 51.0), trace=trace)
    a = {"tick": 1}
    ring = [pe.Event("serve/decode_dispatch", 50.0 + 4.5e-6 - 2e-6, 110e-6,
                     LOOP, a),
            pe.Event("serve/decode_wait", 50.0 + 199.5e-6 - 2e-6, 310e-6,
                     LOOP, a),
            pe.Event("serve/step_decode", 50.0, 0.00095, LOOP,
                     dict(a, batch=3, bucket=4, ctx_tokens=9000,
                          ctx_tokens_windowed=6000, ctx_blocks=64)),
            pe.Event("serve/tick", 50.0, 0.001, LOOP, a)]
    monkeypatch.setattr(pe, "events", lambda: ring)
    monkeypatch.setattr(xn, "of_run", lambda o: names)
    return obs


def test_paged_kernel_roofline_reads_bytes_from_the_span_and_time_by_name(run):
    need = 2 * (2 * 6000 * 8 * 128 * 2)          # layers x K and V x bf16
    expected = 100 * (need / 819e9) / 300e-6
    assert metric("paged_kernel_roofline").read(run) == pytest.approx(expected)


def test_moe_expert_share_reads_the_scope(run):
    busy = 300e-6 + 200e-6 + 40e-6
    assert metric("moe_expert_share").read(run) == \
        pytest.approx(100 * 200e-6 / busy)


def test_idle_attributed_and_skew_and_fills(run):
    # idle in the 1 ms window: 21 us before the kernel, 80 us to the matmul
    # (inside decode_wait), 250 us to the copy (after the wait: step_decode
    # alone holds it), 110 us after (serve/tick or nothing)
    share = metric("tick_idle_attributed_share").read(run)
    assert share == metric("prefill_idle_attributed_share").read(run)
    gaps = tr.idle_gaps(run.trace, run.trace.window, 0)
    named = sum(b - a for a, b in gaps
                if tr.span_at(pe.events(), (a + b) / 2)
                in ("serve/decode_dispatch", "serve/decode_wait"))
    assert 0 < named < sum(b - a for a, b in gaps)
    assert share == pytest.approx(100 * named / sum(b - a for a, b in gaps))
    assert metric("trace_clock_skew_us").read(run) == pytest.approx(2.0)
    assert metric("decode_bucket_fill").read(run) == pytest.approx(75.0)
    assert metric("prefill_bucket_fill").read(run) is None


def test_flash_rooflines_split_forward_and_backward(run, monkeypatch):
    ops = [xn.NamedOp(f"{k}.{i}", 50.0001 + i * 1e-4, dur, 0,
                      "jit_train_batch_step", 'custom-call(), '
                      'custom_call_target="tpu_custom_call"', "custom-call")
           for i, (k, dur) in enumerate([("flash_fwd", 2e-5), ("flash_fwd", 2e-5),
                                         ("flash_bwd_dq", 3e-5),
                                         ("flash_bwd_dkv", 5e-5),
                                         ("attn", 9e-5)])]
    monkeypatch.setattr(xn, "of_run",
                        lambda o: xn.Names(ops, [tr.Span("bench/clock_sync",
                                                         50.0, 0.0005)], [0]))
    run.train = {"tokens_per_step": 1024, "seq_len": 512, "steps": 1,
                 "remat": True}
    run.cell.config["sliding_window"] = None
    pairs = 512 * 513 // 2
    fwd = 2 * 2 * (4 * 128 * 2 * 32 * pairs)     # layers x remat x FLOPs
    bwd = 2 * (8 * 128 * 2 * 32 * pairs)
    assert metric("flash_fwd_roofline").read(run) == \
        pytest.approx(100 * fwd / 197e12 / 4e-5)
    assert metric("flash_bwd_roofline").read(run) == \
        pytest.approx(100 * bwd / 197e12 / 8e-5)


def test_a_program_that_names_nothing_gives_every_reader_nothing(run,
                                                                 monkeypatch):
    """The parent commit under these readers: spans without ``tick`` or
    counts, kernels called ``attn.71``, no scope, nothing mirrored."""
    bare = [pe.Event("serve/step_decode", 50.0, 0.00095, LOOP, {"batch": 3}),
            pe.Event("serve/tick", 50.0, 0.001, LOOP, {"tick": 1})]
    monkeypatch.setattr(pe, "events", lambda: bare)
    names = xn.of_run(run)
    plain = xn.Names(
        [xn.NamedOp("attn.71", o.start, o.dur, 0, o.program, o.detail,
                    o.kind) for o in names.ops],
        [s for s in names.host if s.name == "bench/clock_sync"], [0])
    monkeypatch.setattr(xn, "of_run", lambda o: plain)
    run.train = {"tokens_per_step": 1024, "seq_len": 512, "steps": 1,
                 "remat": True}
    for name in ("decode_bucket_fill",
                 "prefill_bucket_fill", "paged_kernel_roofline",
                 "moe_expert_share", "flash_fwd_roofline",
                 "flash_bwd_roofline", "trace_clock_skew_us"):
        assert metric(name).read(run) is None, name
    # the idle share is a number there too: 0% named by a leaf phase
    assert metric("tick_idle_attributed_share").read(run) == 0.0
    # and an untraced run has no trace to read
    run.trace = None
    assert named_readers.idle_attributed_share(run) is None
    monkeypatch.undo()
    assert xn.of_run(run) is None


# --- a trace recorded on the chip (record_named_trace.py) ----------------------

LIVE = ("serve/engine_step", "serve/plan", "serve/prefill_chunk",
        "serve/decode_build", "serve/decode_dispatch", "serve/decode_wait",
        "serve/decode_commit", "serve/step_finish")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A toy server's seven ticks and one flash forward and backward on a
    v5e, profiled with the program's tracer on: (names from the file, the
    ring's spans, the trace as the harness loads it)."""
    data = REPO / "tests" / "benchmarks" / "data"
    path = tmp_path_factory.mktemp("named") / "named-serve.xplane.pb"
    path.write_bytes(gzip.decompress(
        (data / "named-serve.xplane.pb.gz").read_bytes()))
    doc = json.loads((data / "named-serve.events.json").read_text())
    assert doc["device_kind"] == "TPU v5 lite"
    ring = [pe.Event(name, ts, dur, tid, args)
            for name, ph, ts, dur, tid, args in doc["events"] if ph == "X"]
    names = xn.read(str(path))
    trace = tr.read_xplane(str(path))
    # what profile.Profiled.load does: first mark <-> first stamp
    first = min(s.start for s in trace.spans if s.name == "clock_sync")
    shift = doc["sync"][0] - first
    trace.ops = [tr.Op(o.name, o.start + shift, o.dur, o.device, o.program,
                       o.detail, o.kind, o.overlapped) for o in trace.ops]
    trace.spans = [tr.Span(s.name, s.start + shift, s.dur)
                   for s in trace.spans]
    trace.window = tuple(doc["window"])
    return names, ring, trace


def test_recorded_trace_names_the_kernels_and_the_scopes(recorded):
    names, _, _ = recorded
    kernels = {}
    for o in names.ops:
        if xn.kernel_of(o):
            kernels.setdefault(xn.kernel_of(o), []).append(o)
    assert set(kernels) == {"paged_attention", "flash_fwd", "flash_bwd_dq",
                            "flash_bwd_dkv"}
    assert all("/attn/paged/" in o.scope for o in kernels["paged_attention"])
    assert {o.program for o in kernels["paged_attention"]} == {
        "jit_decode_step_g", "jit_prefill_chunk_g"}
    for scope in ("embed", "attn/qkv", "attn/kv_write", "attn/paged",
                  "attn/out", "mlp", "lm_head"):
        assert any(f"/{scope}/" in o.scope for o in names.ops), scope


def test_recorded_profile_holds_the_programs_spans_beside_the_device(recorded):
    """The operator's view: every live span of the ring is in the profiler's
    host plane too, once each, and within microseconds of its ring stamp."""
    names, ring, trace = recorded
    for name in LIVE:
        on_ring = sum(e.name == name for e in ring)
        assert on_ring and on_ring == sum(s.name == name for s in names.host)
    shift = xn.shift_to_monotonic(names, trace)
    skew = xn.clock_skew_s(names, ring, shift, trace.window)
    assert skew is not None and skew < 100e-6


def test_recorded_decode_ticks_hold_their_paged_calls(recorded):
    names, ring, trace = recorded
    shift = xn.shift_to_monotonic(names, trace)
    ticks = [e for e in ring if e.name == pe.STEP_DECODE]
    assert len(ticks) == 6
    # the device's own clock is tied to the host's by the profiler to within
    # some hundred microseconds: nothing to a served tick of 49 ms, but these
    # toy ticks last 3 ms and their kernels 7 us, so a call may seem to start
    # that much before its tick
    slack = 5e-4
    for t in ticks:
        calls = [o for o in names.ops if xn.kernel_of(o) == "paged_attention"
                 and "decode_step" in o.program
                 and t.start - slack <= o.start + shift
                 and o.end + shift <= t.end]
        assert len(calls) == 2                # the toy model's two layers
        assert t.arg("batch") <= t.arg("bucket")
        assert t.arg("ctx_tokens_windowed") <= t.arg("ctx_tokens")
    gaps = tr.idle_gaps(trace, trace.window, 0)
    share = pe.attributed_idle_share(gaps, pe.loop_thread(ring))
    assert 0.0 < share <= 100.0
