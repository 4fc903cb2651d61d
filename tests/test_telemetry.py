"""dstrace telemetry tests (tracer core + cross-subsystem instrumentation).

Contracts pinned here:

  round-trip   : spans/instants -> valid Chrome-trace JSON (Perfetto object
                 format), nesting by ts/dur containment, step correlation
                 keys, monotonic ids, bounded ring with exact drop count
  train        : sync and async modes emit the SAME per-step dispatch spans;
                 async additionally emits drain + reconciled-window spans
                 whose step counts tie out
  serving      : request lifecycle spans alone reproduce TTFT exactly as
                 the serving metrics measured it
  resilience   : signal path stays DS005-clean and emits an append-only
                 breadcrumb (no sink fan-out from handler context);
                 quarantine bundles embed a Perfetto-loadable trace tail
  end-to-end   : a chaos run under tracing produces dispatch/drain/prefetch/
                 checkpoint/comm spans and resilience instants in ONE trace
                 (the PR's acceptance shape)
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.simple import SimpleModel, random_batch
from deepspeed_tpu.telemetry import get_tracer, request_tid
from deepspeed_tpu.telemetry.tracer import HOST_GC_TID, Tracer
from serving_fakes import ResidentEngine

pytestmark = pytest.mark.telemetry

CFG = {
    "train_batch_size": 8,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
}


@pytest.fixture
def tracing():
    """Enable the process tracer for one test, fully restored afterwards
    (other suites rely on the disabled no-op fast path)."""
    t = get_tracer()
    t.clear()
    t.detach_sink()
    capacity = t.capacity       # a test may shrink the ring: later files
    t.configure(enabled=True)   # on this worker need it whole
    try:
        yield t
    finally:
        t.configure(enabled=False, capacity=capacity)
        t.detach_sink()
        t.clear()


def _engine(seed=1, extra=None):
    cfg = dict(CFG)
    if extra:
        cfg.update(extra)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=32), config=cfg,
        example_batch=random_batch(4), seed=seed)
    return engine


def _spans(trace, name=None):
    out = [e for e in trace["traceEvents"]
           if e.get("ph") == "X" and (name is None or e["name"] == name)]
    return out


def _instants(trace, name=None):
    return [e for e in trace["traceEvents"]
            if e.get("ph") == "i" and (name is None or e["name"] == name)]


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------
def test_trace_round_trip_valid_chrome_json(tmp_path, tracing):
    with tracing.span("outer", cat="t", step=3):
        with tracing.span("inner", cat="t", step=3):
            time.sleep(0.002)
    tracing.instant("marker", step=3, detail="x")
    path = str(tmp_path / "trace.json")
    tracing.export_chrome(path)
    trace = json.loads(open(path).read())     # round-trips as strict JSON
    assert isinstance(trace["traceEvents"], list)
    assert trace["displayTimeUnit"] == "ms"
    outer, = _spans(trace, "outer")
    inner, = _spans(trace, "inner")
    # nesting: same thread track, inner contained within outer's ts window
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    # step correlation + monotonic ids
    assert outer["args"]["step"] == 3 and inner["args"]["step"] == 3
    marker, = _instants(trace, "marker")
    assert marker["args"]["step"] == 3 and marker["s"] == "t"
    assert inner["args"]["id"] < outer["args"]["id"] < marker["args"]["id"]
    # thread metadata present so Perfetto labels the track
    assert any(e.get("ph") == "M" and e["name"] == "thread_name"
               for e in trace["traceEvents"])


def test_ring_bounded_with_exact_drop_count():
    t = Tracer(capacity=32)
    t.enabled = True
    for i in range(100):
        t.instant(f"e{i}")
    snap = t.events_snapshot()
    assert len(snap) == 32
    assert t.dropped() == 68
    assert snap[-1][1] == "e99"           # newest survives
    # clear() discards, it does not evict: drop count survives unchanged
    # and cleared events never masquerade as ring pressure
    t.clear()
    for i in range(5):
        t.instant(f"post{i}")
    assert len(t.events_snapshot()) == 5
    assert t.dropped() == 68
    # resizing the ring keeps every retained event
    t.configure(capacity=64)
    assert len(t.events_snapshot()) == 5


def test_disabled_tracer_is_noop():
    t = Tracer()
    s1, s2 = t.span("a"), t.span("b", step=1)
    assert s1 is s2                       # shared no-op context, no allocs
    with s1:
        pass
    t.instant("x", step=1)
    t.complete("y", 0.5)
    assert t.events_snapshot() == []


def test_tail_slice_and_summary(tracing):
    tracing.complete("old", 0.001, end_ts=time.monotonic() - 120.0)
    tracing.complete("fresh", 0.002)
    tail = tracing.tail(60.0)
    assert [e[1] for e in tail] == ["fresh"]
    summ = tracing.summary()
    assert summ["fresh"]["count"] == 1
    assert summ["fresh"]["total_s"] == pytest.approx(0.002)
    assert set(summ) == {"old", "fresh"}


def test_dstpu_trace_env_activation(tmp_path):
    """DSTPU_TRACE=path turns tracing on at first use and dumps at exit."""
    out = str(tmp_path / "env_trace.json")
    code = (
        "from deepspeed_tpu.telemetry import get_tracer\n"
        "t = get_tracer()\n"
        "assert t.enabled\n"
        "with t.span('probe', step=1):\n"
        "    pass\n")
    env = dict(os.environ, DSTPU_TRACE=out)
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
    trace = json.load(open(out))
    assert _spans(trace, "probe")


def test_report_cli_renders_top_spans(tmp_path, tracing, capsys):
    with tracing.span("engine/dispatch", cat="train", step=0):
        time.sleep(0.001)
    tracing.instant("chaos/nan", step=0)
    path = str(tmp_path / "t.json")
    tracing.export_chrome(path)
    from deepspeed_tpu.telemetry.report import main as report_main
    assert report_main([path]) == 0
    text = capsys.readouterr().out
    assert "engine/dispatch" in text and "chaos/nan" in text
    assert report_main([path, "--json"]) == 0
    agg = json.loads(capsys.readouterr().out)
    assert agg["spans"][0]["name"] == "engine/dispatch"
    assert agg["instants"]["chaos/nan"] == 1
    assert report_main([str(tmp_path / "missing.json")]) == 2


# ---------------------------------------------------------------------------
# comms logging satellites
# ---------------------------------------------------------------------------
def test_calc_bw_degenerate_guards():
    from deepspeed_tpu.comm.comms_logging import calc_bw
    # zero/negative duration and negative size never produce inf/garbage
    assert calc_bw("all_reduce", 1 << 20, 0.0, 8) == (0.0, 0.0)
    assert calc_bw("all_reduce", 1 << 20, -1.0, 8) == (0.0, 0.0)
    assert calc_bw("all_reduce", -5, 1.0, 8) == (0.0, 0.0)
    # world==1: busbw == algbw, not ring-factor zero
    alg, bus = calc_bw("all_reduce", 1 << 20, 1.0, 1)
    assert alg == bus == float(1 << 20)
    alg, bus = calc_bw("all_gather", 1 << 20, 1.0, 1)
    assert bus == alg
    # the ring factors still apply for world > 1
    alg, bus = calc_bw("all_reduce", 1 << 20, 1.0, 4)
    assert bus == pytest.approx(alg * 1.5)


def test_comms_per_op_totals_and_env_rows(tracing):
    from deepspeed_tpu.comm.comms_logging import CommsLogger
    cl = CommsLogger()
    cl.configure(enabled=True)
    cl.record_traced("all_reduce", 1000, 4)
    cl.record_traced("all_reduce", 500, 4)
    with cl.timed("broadcast", 2000, 2):
        time.sleep(0.001)
    totals = cl.per_op_totals()
    assert totals["all_reduce"] == {"count": 2, "bytes": 1500.0,
                                    "wire_bytes": 1500.0, "seconds": 0.0}
    assert totals["broadcast"]["count"] == 1
    assert totals["broadcast"]["seconds"] > 0
    rows = dict(cl.env_report_rows())
    assert "comms[all_reduce]" in rows and "comms[broadcast]" in rows
    # traced ops emit comm instants; timed ops emit comm spans with bw args
    counts = tracing.instant_counts(prefix="comm/")
    assert counts["comm/all_reduce"] == 2
    span = [e for e in tracing.events_snapshot()
            if e[1] == "comm/broadcast" and e[3] == "X"]
    assert span and span[0][7]["bytes"] == 2000
    assert "busbw_gbps" in span[0][7]
    # env_report surface never dies and includes the comms section
    from deepspeed_tpu.env_report import comms_report, trace_report
    assert comms_report()
    assert any("dstrace" in k for k, _ in trace_report())


# ---------------------------------------------------------------------------
# monitor events sink
# ---------------------------------------------------------------------------
def _csv_master(tmp_path):
    from deepspeed_tpu.config.config import (CometConfig, CSVConfig,
                                             TensorBoardConfig, WandbConfig)
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    cfg = types.SimpleNamespace(
        csv_monitor=CSVConfig(enabled=True, output_path=str(tmp_path),
                              job_name="events"),
        tensorboard=TensorBoardConfig(enabled=False),
        wandb=WandbConfig(enabled=False),
        comet=CometConfig(enabled=False))
    return MonitorMaster(cfg)


def test_monitor_events_sink_receives_instants(tmp_path, tracing):
    mon = _csv_master(tmp_path)
    assert mon.enabled
    tracing.attach_sink(mon.write_instant)
    tracing.instant("chaos/nan", step=5)             # fans out
    tracing.instant("resilience/quiet", step=6, fanout=False)  # must not
    tracing.instant("no_step_marker")                # no step -> no fan-out
    written = {p.stem for p in (tmp_path / "events").glob("*.csv")}
    assert "Events_chaos_nan" in written
    assert "Events_resilience_quiet" not in written
    rows = open(tmp_path / "events" / "Events_chaos_nan.csv").read()
    assert "5,1.0" in rows


# ---------------------------------------------------------------------------
# nvtx routing
# ---------------------------------------------------------------------------
def test_nvtx_routes_through_tracer(tracing):
    from deepspeed_tpu.utils import nvtx

    @nvtx.instrument(name="scaled")
    def f(x):
        return x * 2

    assert f(3) == 6
    with nvtx.annotate("outer_range"):
        pass
    ctx = nvtx.range_push("pushed")
    nvtx.range_pop(ctx)
    names = {e[1] for e in tracing.events_snapshot()}
    assert {"scaled", "outer_range", "pushed"} <= names


def test_nvtx_noop_when_tracing_off():
    from deepspeed_tpu.utils import nvtx
    t = get_tracer()
    assert not t.enabled
    before = len(t.events_snapshot())
    with nvtx.annotate("quiet"):
        pass

    @nvtx.instrument
    def g():
        return 1

    assert g() == 1
    assert len(t.events_snapshot()) == before


# ---------------------------------------------------------------------------
# engine: sync vs async span parity
# ---------------------------------------------------------------------------
def _batches(n, bs=8):
    return iter([random_batch(bs, seed=i) for i in range(n)])


def test_sync_vs_async_dispatch_drain_span_parity(tracing):
    steps = 8
    engine = _engine(seed=1)
    it = _batches(steps)
    for _ in range(steps):
        engine.train_batch(data_iter=it)
    sync_events = tracing.events_snapshot()
    sync_dispatch = [e for e in sync_events if e[1] == "engine/dispatch"]
    assert len(sync_dispatch) == steps
    assert all(e[7]["mode"] == "sync" for e in sync_dispatch)
    assert not [e for e in sync_events if e[1] == "engine/drain"]
    # step correlation: one dispatch per engine step, in order
    assert [e[7]["step"] for e in sync_dispatch] == list(range(steps))

    tracing.clear()
    engine = _engine(seed=1, extra={
        "async_pipeline": {"enabled": True, "sync_every": 4}})
    it = _batches(steps)
    for _ in range(steps):
        engine.train_batch(data_iter=it)
    engine.flush_metrics()
    async_events = tracing.events_snapshot()
    async_dispatch = [e for e in async_events if e[1] == "engine/dispatch"]
    # PARITY: async mode emits the same per-step dispatch spans...
    assert len(async_dispatch) == steps
    assert [e[7]["step"] for e in async_dispatch] == list(range(steps))
    assert all(e[7]["mode"] == "async" for e in async_dispatch)
    # ...plus drains whose per-drain step counts tie out to every step
    drains = [e for e in async_events if e[1] == "engine/drain"]
    assert len(drains) == steps // 4
    assert sum(e[7]["steps"] for e in drains) == steps
    reconciled = [e for e in async_events
                  if e[1] == "engine/steps_reconciled"]
    assert sum(e[7]["steps"] for e in reconciled) == steps
    # the reconciled windows cover real wall time (dispatch-gap vs step time)
    assert all(e[5] > 0 for e in reconciled)


def test_dump_trace_and_summary_from_engine(tmp_path, tracing):
    engine = _engine(seed=3)
    it = _batches(2)
    for _ in range(2):
        engine.train_batch(data_iter=it)
    path = str(tmp_path / "engine_trace.json")
    trace = engine.dump_trace(path)
    assert os.path.exists(path)
    assert _spans(trace, "engine/dispatch")
    assert _spans(trace, "comm/h2d")
    summ = engine.trace_summary(prefix="engine/")
    assert summ["engine/dispatch"]["count"] == 2


# ---------------------------------------------------------------------------
# serving: TTFT derivable from the trace alone
# ---------------------------------------------------------------------------
class _OneTokenPerStepEngine(ResidentEngine):
    """Engine double: every resident sequence yields one token per step."""


def test_serving_request_spans_reproduce_ttft(tracing):
    from deepspeed_tpu.serving import InferenceServer, ServingConfig
    server = InferenceServer(_OneTokenPerStepEngine(),
                             ServingConfig(idle_poll_s=0.001)).start()
    try:
        req = server.submit([1, 2, 3], max_new_tokens=4)
        toks = req.result(timeout=30.0)
        assert len(toks) == 4
    finally:
        server.stop(drain_timeout=5.0)
    trace = tracing.to_chrome()
    tid = request_tid(req.uid)
    queued, = [e for e in _spans(trace, "serve/queued")
               if e["tid"] == tid]
    prefill, = [e for e in _spans(trace, "serve/prefill")
                if e["tid"] == tid]
    decode, = [e for e in _spans(trace, "serve/decode")
               if e["tid"] == tid]
    # TTFT from the trace alone == the metric the server recorded
    ttft_trace = (queued["dur"] + prefill["dur"]) / 1e6
    assert ttft_trace == pytest.approx(req.ttft_s, rel=1e-6, abs=1e-6)
    # TPOT derivable too: decode span / (tokens - 1)
    assert decode["args"]["tokens"] == 4
    tpot_trace = decode["dur"] / 1e6 / 3
    assert tpot_trace == pytest.approx(req.tpot_s, rel=1e-6, abs=1e-6)
    # terminal instant on the same per-request track
    finished = [e for e in _instants(trace, "serve/finished")
                if e["tid"] == tid]
    assert finished and finished[0]["args"]["uid"] == req.uid
    # /metrics grows tracer-sourced span summaries
    prom = server.metrics.prometheus_text()
    assert 'dstpu_trace_span_seconds{span="serve/decode"' in prom
    assert 'dstpu_trace_span_seconds_count{span="serve/queued"} 1' in prom


# ---------------------------------------------------------------------------
# resilience: signal-path safety + bundle trace tail
# ---------------------------------------------------------------------------
@pytest.mark.lint
def test_signal_path_stays_ds005_clean():
    """The instrumented SIGTERM handler (tracer breadcrumb included) must
    carry no new non-reentrant work — DS005 over the runner file must only
    show the two recorded inline suppressions, no findings."""
    from deepspeed_tpu.tools.dslint import lint_paths
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = lint_paths(
        [os.path.join(root, "deepspeed_tpu/resilience/runner.py")],
        root=root, select=["DS005"])
    assert not result.findings, [str(f) for f in result.findings]


def test_signal_breadcrumb_is_append_only(tmp_path, tracing):
    """The handler's instant skips the monitor sink (fanout=False): no I/O
    can happen in handler context even with a sink attached."""
    from deepspeed_tpu.resilience import FaultTolerantRunner
    engine = _engine(seed=2)
    sink_calls = []
    tracing.attach_sink(lambda name, step: sink_calls.append(name))
    runner = FaultTolerantRunner(engine, save_dir=str(tmp_path / "ckpt"))
    try:
        runner._on_signal(signal.SIGTERM, None)
        assert runner.preempted
        crumbs = [e for e in tracing.events_snapshot()
                  if e[1] == "resilience/preempt_signal"]
        assert crumbs and crumbs[0][7]["signum"] == signal.SIGTERM
        assert sink_calls == []           # append-only: sink untouched
    finally:
        runner.close()


@pytest.mark.chaos
def test_quarantine_bundle_embeds_trace_tail(tmp_path, tracing):
    from deepspeed_tpu.resilience import (ChaosConfig, ChaosMonkey,
                                          FaultTolerantRunner,
                                          QuarantineError, ResilienceConfig)
    engine = _engine(seed=5)
    rc = ResilienceConfig(
        step_guard={"backoff_after": 0, "quarantine_after": 2},
        diagnostics_dir=str(tmp_path / "diag"))
    chaos = ChaosMonkey(ChaosConfig(seed=1, nan_prob=1.0))
    runner = FaultTolerantRunner(engine, save_dir=str(tmp_path / "ckpt"),
                                 config=rc, chaos=chaos,
                                 install_signal_handlers=False)
    try:
        with pytest.raises(QuarantineError) as ei:
            runner.run(num_steps=5,
                       batch_fn=lambda step: random_batch(8, seed=step))
        bundle = ei.value.bundle_path
        tail_path = os.path.join(bundle, "trace_tail.json")
        assert os.path.exists(tail_path)
        tail = json.load(open(tail_path))
        names = {e["name"] for e in tail["traceEvents"]}
        # the slice holds the story: chaos injections, guard trips, the
        # dispatches that carried them, and the final quarantine marker
        assert "chaos/nan" in names
        assert "resilience/bad_step" in names
        assert "resilience/quarantine" in names
        assert "engine/dispatch" in names
    finally:
        runner.close()


# ---------------------------------------------------------------------------
# end-to-end: one trace, every subsystem (the acceptance shape)
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_end_to_end_chaos_trace_has_all_span_families(tmp_path, tracing):
    from deepspeed_tpu.resilience import (ChaosConfig, ChaosMonkey,
                                          FaultTolerantRunner,
                                          ResilienceConfig)
    engine = _engine(seed=7, extra={
        "async_pipeline": {"enabled": True, "sync_every": 2,
                           "prefetch": True}})
    rc = ResilienceConfig(
        autosave={"every_steps": 4, "io_backoff_s": 0.01},
        diagnostics_dir=str(tmp_path / "diag"))
    chaos = ChaosMonkey(ChaosConfig(seed=7, nan_steps=frozenset({2})))
    runner = FaultTolerantRunner(engine, save_dir=str(tmp_path / "ckpt"),
                                 config=rc, chaos=chaos,
                                 install_signal_handlers=False)
    try:
        result = runner.run(num_steps=6,
                            batch_fn=lambda step: random_batch(8, seed=step))
        assert result.steps_completed == 6
    finally:
        runner.close()
    path = str(tmp_path / "full_trace.json")
    trace = engine.dump_trace(path)
    names = {e["name"] for e in trace["traceEvents"]}
    # every span family of the unified timeline, in ONE dump
    assert "engine/dispatch" in names          # train dispatch
    assert "engine/drain" in names             # deferred readback
    assert "engine/steps_reconciled" in names  # true step-time windows
    assert "comm/h2d" in names                 # batch staging volume
    assert "ckpt/save" in names                # autosave boundary
    assert "chaos/nan" in names                # chaos injection instant
    assert "resilience/bad_step" in names      # guard trip instant
    # Perfetto-loadable: strict JSON from disk with the object envelope
    loaded = json.load(open(path))
    assert loaded["traceEvents"] and loaded["displayTimeUnit"] == "ms"
    # and the text report renders it
    from deepspeed_tpu.telemetry.report import aggregate, load_events
    rows, instants, wall = aggregate(load_events(path))
    assert wall > 0 and any(r["name"] == "engine/dispatch" for r in rows)
    assert instants.get("chaos/nan", 0) >= 1


# ---------------------------------------------------------------------------
# dslint proves the tracer itself never syncs
# ---------------------------------------------------------------------------
@pytest.mark.lint
def test_hotpath_taint_covers_tracer_emit_helpers(package_callgraph,
                                                 hot_reached):
    g = package_callgraph
    # the emit surface every instrumented subsystem calls per step/tick
    # stays inside the DS002 taint (no host sync can grow into it)
    for qn in ("Tracer.span", "Tracer.instant", "Tracer.complete",
               "Tracer._emit", "_Span.__enter__", "_Span.__exit__"):
        key = g.resolve("deepspeed_tpu/telemetry/tracer.py", qn)
        assert key is not None, f"{qn} gone from tracer.py"
        assert key in hot_reached, f"{qn} fell out of the hot taint"


def test_tracer_emit_is_thread_safe(tracing):
    """Concurrent emitters (serve loop / prefetch worker / watchdog shapes)
    never corrupt the ring: every event lands, ids stay unique."""
    n_threads, per = 8, 200
    tracing.configure(capacity=n_threads * per + 16)

    def emit(k):
        for i in range(per):
            tracing.instant(f"t{k}", fanout=False, i=i)

    threads = [threading.Thread(target=emit, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # the emitters' tuples set the interpreter collecting; its ``host/gc``
    # spans share the ring and the ids
    snap = tracing.events_snapshot()
    assert sum(e[6] != HOST_GC_TID for e in snap) == n_threads * per
    ids = [e[0] for e in snap]
    assert len(set(ids)) == len(ids)


# ---------------------------------------------------------------------------
# host/gc: the interpreter's collections as spans on a track of their own
# ---------------------------------------------------------------------------
def _gc_spans(tracer):
    return [e for e in tracer.events_snapshot() if e[1] == "host/gc"]


def test_a_full_collection_is_one_host_gc_span_on_the_gc_track():
    import gc
    t = Tracer().configure(enabled=True)
    t0 = time.monotonic()
    gc.collect()
    t1 = time.monotonic()
    full = [e for e in _gc_spans(t) if e[7]["generation"] == 2]
    assert len(full) == 1
    (_, name, cat, ph, ts, dur, tid, args), = full
    assert (cat, ph, tid) == ("host", "X", HOST_GC_TID)
    assert t0 <= ts and ts + dur <= t1 and dur > 0
    assert args["thread"] == threading.get_ident()
    assert args["collected"] >= 0
    assert set(args) == {"generation", "collected", "thread", "gen0",
                         "gen0_s"}
    # every host/gc event sits on that one track, whichever thread ran it
    done = threading.Thread(target=gc.collect)
    done.start()
    done.join()
    spans = _gc_spans(t)
    assert {e[6] for e in spans} == {HOST_GC_TID}
    assert {e[7]["thread"] for e in spans} >= {threading.get_ident(),
                                               done.ident}
    # the export labels the track
    names = {e["args"]["name"] for e in t.to_chrome()["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "host-gc" in names
    t.configure(enabled=False)


def test_short_young_collections_are_counted_not_emitted():
    import gc
    t = Tracer().configure(enabled=True)
    was = gc.get_threshold()
    gc.collect()
    t.clear()
    try:
        # no older generation joins in: every collection is the youngest's
        gc.set_threshold(50, 1_000_000, 1_000_000)
        keep = [[] for _ in range(5000)]
    finally:
        gc.set_threshold(*was)
    young = [e for e in _gc_spans(t) if e[7]["generation"] == 0]
    # some hundred ran (one every 50 lists); only one that took over a
    # millisecond would have been emitted
    assert all(e[5] >= 1e-3 for e in young)
    assert len(young) < 10
    gc.collect()
    last = _gc_spans(t)[-1]
    assert last[7]["generation"] == 2
    counted = sum(e[7]["gen0"] for e in _gc_spans(t))
    assert counted + len(young) >= 0.9 * (5000 // 50)
    assert 0.0 < sum(e[7]["gen0_s"] for e in _gc_spans(t)) < 1.0
    # what rode on one span is not counted again on the next
    gc.collect()
    assert _gc_spans(t)[-1][7]["gen0"] <= 1
    assert len(keep) == 5000
    t.configure(enabled=False)


def test_the_gc_callback_is_there_only_while_tracing_is_on():
    import gc
    before = list(gc.callbacks)
    t = Tracer()
    assert gc.callbacks == before          # made, not enabled: nothing
    t.configure(enabled=True)
    t.configure(enabled=True)              # twice on: still one entry
    assert len(gc.callbacks) == len(before) + 1
    assert t._gc_hook in gc.callbacks
    t.configure(enabled=False)
    assert gc.callbacks == before
    gc.collect()
    assert _gc_spans(t) == []
    # a tracer that goes away while on takes its entry with it
    t.configure(enabled=True)
    hook = t._gc_hook
    del t
    gc.collect()
    assert hook not in gc.callbacks and gc.callbacks == before


def test_the_process_tracer_watches_collections_while_enabled(tracing):
    import gc
    assert tracing._gc_hook in gc.callbacks
    gc.collect()
    assert any(e[7]["generation"] == 2 for e in _gc_spans(tracing))
    # registered like any other name, as a retro event
    from deepspeed_tpu.telemetry.names import SERVE_STAGE_OF, TRACE_NAMES
    assert TRACE_NAMES["host/gc"] == ("complete",)
    assert TRACE_NAMES["serve/idle"] == ("complete",)
    assert "serve/idle" not in SERVE_STAGE_OF
    tracing.configure(enabled=False)
    assert tracing._gc_hook not in gc.callbacks
