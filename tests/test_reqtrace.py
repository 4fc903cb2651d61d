"""Request-tracing tests: the deterministic SLO histograms (golden
buckets — no wall clock anywhere), the dstpu_req_* /metrics families,
the reqtrace stitcher (synthetic router+replica+flight dumps with exact
tie-out arithmetic), TickLedger request attribution, and the
env_report rows.

Every duration in this file is a constructed constant (powers of two or
TickLedger ceil-div units), so bucket verdicts and tie-out errors are
bit-identical on every platform — the histogram's whole design point.
"""

import json
import os

import pytest

from deepspeed_tpu.telemetry import hist as dshist
from deepspeed_tpu.telemetry import reqtrace
from deepspeed_tpu.telemetry.names import REQ_STAGE_OF

pytestmark = pytest.mark.telemetry


# ---------------------------------------------------------------------------
# LogHistogram: golden buckets, exact and platform-independent
# ---------------------------------------------------------------------------
def test_log2_bounds_are_exact_powers():
    bounds = dshist.log2_bounds()
    assert len(bounds) == (dshist.DEFAULT_HIGH_EXP
                           - dshist.DEFAULT_LOW_EXP + 1)
    assert bounds[0] == 2.0 ** -20
    assert bounds[-1] == 64.0
    # strictly doubling — each bound IEEE-754-exact
    for a, b in zip(bounds, bounds[1:]):
        assert b == a * 2.0


def test_golden_bucket_indices():
    h = dshist.LogHistogram()
    # le-inclusive: a value exactly on a bound lands IN that bucket
    assert h.bucket_index(0.25) == h.bounds.index(0.25)
    assert h.bucket_index(0.2500001) == h.bounds.index(0.5)
    # zero and negatives land in the first bucket
    assert h.bucket_index(0.0) == 0
    assert h.bucket_index(-1.0) == 0
    # over the top bound -> the +Inf bucket (index == len(bounds))
    assert h.bucket_index(65.0) == len(h.bounds)


def test_golden_counts_sum_and_quantiles():
    h = dshist.LogHistogram()
    # durations derived from tick units, not clocks: 3 obs at 0.25s,
    # 1 at 1.0s, 1 saturating
    for v in (0.25, 0.25, 0.25, 1.0, 100.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == 0.25 * 3 + 1.0 + 100.0
    assert h.counts[h.bounds.index(0.25)] == 3
    assert h.counts[h.bounds.index(1.0)] == 1
    assert h.inf_count == 1
    # quantiles are bucket upper edges at the repo-wide exact rank rule
    assert h.quantile(0.5) == 0.25
    assert h.quantile(0.79) == 1.0
    # +Inf hits floor at the top finite bound, never a fabricated value
    assert h.quantile(0.99) == 64.0
    assert dshist.LogHistogram().quantile(0.5) == 0.0


def test_merge_delta_and_snapshot_roundtrip():
    a = dshist.LogHistogram()
    b = dshist.LogHistogram()
    a.observe_many([0.125, 0.125, 2.0])
    b.observe_many([0.125, 4.0])
    merged = dshist.LogHistogram.from_snapshot(a.snapshot())
    merged.merge(b)
    assert merged.count == 5
    assert merged.counts[merged.bounds.index(0.125)] == 3
    delta = merged.delta_from(a)
    assert delta.count == b.count
    assert delta.counts == b.counts
    assert delta.sum == pytest.approx(b.sum)
    # differing bounds are a programming error, loudly
    with pytest.raises(ValueError):
        a.merge(dshist.LogHistogram(bounds=(1.0, 2.0)))


def test_prometheus_histogram_lines_shape():
    h = dshist.LogHistogram(bounds=(0.5, 1.0))
    h.observe_many([0.5, 0.75, 3.0])
    lines = dshist.prometheus_histogram_lines(
        "dstpu_req_test_seconds", h, help_text="test family")
    text = "\n".join(lines)
    # DS008 shape: exactly one TYPE block, declared histogram
    assert text.count("# TYPE dstpu_req_test_seconds histogram") == 1
    # cumulative buckets, le-labelled, +Inf == count
    assert 'le="0.5"} 1' in text
    assert 'le="1.0"} 2' in text
    assert 'le="+Inf"} 3' in text
    assert "dstpu_req_test_seconds_count 3" in text
    assert "dstpu_req_test_seconds_sum" in text


# ---------------------------------------------------------------------------
# ServingMetrics: the dstpu_req_* families
# ---------------------------------------------------------------------------
def _finished_request(uid=1, queue_wait=0.25, prefill=0.25, decode=1.0,
                      tokens=3):
    """A terminal Request with CONSTRUCTED timestamps (no sleeping):
    queue_wait/ttft/tpot become exact powers of two."""
    from deepspeed_tpu.serving.request import Request, RequestState
    r = Request(uid, [1, 2, 3, 4], max_new_tokens=tokens)
    r.admit_ts = r.arrival_ts + queue_wait
    r.first_token_ts = r.admit_ts + prefill
    r.finish_ts = r.first_token_ts + decode
    r.tokens = list(range(tokens))
    r.state = RequestState.FINISHED
    return r


def test_serving_metrics_slo_histograms_and_families():
    from deepspeed_tpu.serving.metrics import REQ_HIST_FAMILIES, \
        ServingMetrics
    m = ServingMetrics()
    # queue_wait=0.25, ttft=0.5, tpot = 1.0/(3-1) = 0.5 — all exact bounds
    m.on_finish(_finished_request())
    snap = m.slo_snapshot()
    assert set(snap) == {f for f, _a, _h in REQ_HIST_FAMILIES}
    ttft = dshist.LogHistogram.from_snapshot(
        snap["dstpu_req_ttft_seconds"])
    assert ttft.count == 1
    assert ttft.counts[ttft.bounds.index(0.5)] == 1
    qw = dshist.LogHistogram.from_snapshot(
        snap["dstpu_req_queue_wait_seconds"])
    assert qw.counts[qw.bounds.index(0.25)] == 1
    tpot = dshist.LogHistogram.from_snapshot(
        snap["dstpu_req_tpot_seconds"])
    assert tpot.counts[tpot.bounds.index(0.5)] == 1


def test_serving_metrics_prometheus_exports_req_families():
    from deepspeed_tpu.serving.metrics import REQ_HIST_FAMILIES, \
        ServingMetrics
    m = ServingMetrics()
    m.on_finish(_finished_request())
    text = m.prometheus_text()
    for family, _attr, _help in REQ_HIST_FAMILIES:
        # DS008: exactly one TYPE block per family on the whole page
        assert text.count(f"# TYPE {family} histogram") == 1, family
        assert f'{family}_bucket{{le="+Inf"}}' in text
        assert f"{family}_count" in text
    assert 'dstpu_req_ttft_seconds_bucket{le="0.5"} 1' in text


# ---------------------------------------------------------------------------
# TickLedger request attribution (wall-clock-free units)
# ---------------------------------------------------------------------------
def test_tick_ledger_units_ceil_div():
    from deepspeed_tpu.runtime.sched import TickLedger
    assert TickLedger.units(0, 16) == 0
    assert TickLedger.units(16, 0) == 0
    assert TickLedger.units(1, 16) == 1
    assert TickLedger.units(16, 16) == 1
    assert TickLedger.units(17, 16) == 2


def test_tick_ledger_request_attribution_and_cap():
    from deepspeed_tpu.runtime.sched import TickLedger
    led = TickLedger()
    led.attribute_request(7, prefill_tokens=48, chunks=3)
    led.attribute_request(7, decode_tokens=1)
    led.attribute_request(7, decode_tokens=1)
    entry = led.pop_request(7)
    assert entry == {"ticks": 3, "prefill_tokens": 48, "chunks": 3,
                     "decode_tokens": 2}
    assert led.pop_request(7) is None          # popped == settled
    # FIFO age-out keeps the table bounded at REQUEST_CAP
    for uid in range(TickLedger.REQUEST_CAP + 5):
        led.attribute_request(uid, decode_tokens=1)
    assert len(led.request_ticks) == TickLedger.REQUEST_CAP
    assert led.pop_request(0) is None          # the oldest aged out
    assert led.pop_request(TickLedger.REQUEST_CAP + 4) is not None


# ---------------------------------------------------------------------------
# reqtrace: synthetic stitch with exact tie-out arithmetic
# ---------------------------------------------------------------------------
def _dump(pid, wall_s, events, flight=None):
    """A minimal to_chrome()-shaped dump whose epoch sits at wall time
    ``wall_s`` (monotonic_s == epoch_monotonic_s, so the wall anchor is
    exactly ``wall_s``)."""
    other = {"clock": "monotonic",
             "process": {"rank": 0, "world": 1, "hostname": "host",
                         "pid": pid, "monotonic_s": 50.0, "wall_s": wall_s,
                         "epoch_monotonic_s": 50.0}}
    if flight is not None:
        other["flight"] = flight
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": other}


def _ev(name, ts_us, dur_us, **args):
    return {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
            "cat": "serve", "pid": 0, "tid": 1, "args": args}


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def _drill_dumps(tmp_path):
    """The canonical failover story, in exact microseconds: the router's
    wall envelope [100, 1100]; replica A (pid 20) is killed mid-decode
    (flight dump, ledger only); the router backs off (req/reroute
    [300, 400]); replica B (pid 30, clock +500us vs the router) serves
    queue[500,600] prefill[600,800] decode[800,1050]."""
    router = _dump(10, 1000.0, [
        _ev("req/wall", 100, 1000, trace_id="t1", outcome="finished",
            uid=1, tokens=6),
        _ev("req/reroute", 300, 100, trace_id="t1", uid=1, from_replica=0,
            sent=2, recompute=10),
    ])
    flight = _dump(20, 1000.0001, [], flight={
        "reason": "chaos_replica_kill", "replica_id": 0, "pid": 20,
        "tick": 4,
        "inflight": [{"uid": 3, "trace_id": "t1", "state": "decode",
                      "generated_tokens": 2, "queue_wait_s": 1e-4,
                      "ttft_s": 2e-4,
                      "sched_attribution": {"ticks": 3, "decode_tokens": 2,
                                            "prefill_tokens": 10,
                                            "chunks": 1}}],
        "queued": []})
    replica_b = _dump(30, 1000.0005, [
        _ev("req/queue", 0, 100, trace_id="t1", uid=7),
        _ev("req/prefill", 100, 200, trace_id="t1", uid=7),
        _ev("req/decode", 300, 250, trace_id="t1", uid=7),
        _ev("req/decode", 0, 100, trace_id="nobody-minted-me", uid=9),
    ])
    return [_write(tmp_path, "router.json", router),
            _write(tmp_path, "flight_replica0_20.json", flight),
            _write(tmp_path, "replica_b.json", replica_b)]


def test_stitch_failover_timeline_exact(tmp_path):
    report = reqtrace.stitch_requests(_drill_dumps(tmp_path))
    assert report["alignment"] == "wall_anchor"
    assert report["requests_stitched"] == 1
    assert report["flight_dumps"] == 1
    t1 = report["traces"]["t1"]
    assert t1["wall"]["dur_us"] == 1000.0
    assert t1["wall"]["outcome"] == "finished"
    # the surviving replica's visit chain, on the shared wall axis
    assert [v["pid"] for v in t1["visits"]] == [30]
    # reroute is router-side, so it does not appear as a visit stage
    assert t1["visits"][0]["stages"] == ["queue", "prefill", "decode"]
    # req/reroute links the dead replica to the survivor
    assert t1["reroutes"] == 1
    # the killed attempt is visible, recovered from the flight ledger
    assert t1["flight_recovered"]
    assert t1["recovered"][0]["reason"] == "chaos_replica_kill"
    assert t1["recovered"][0]["generated_tokens"] == 2
    # EXACT tie-out: phases 100+200+250 + reroute 100 = 650us, all
    # disjoint inside the envelope -> covered == span_sum, error == 0
    assert t1["span_sum_us"] == 650.0
    assert t1["covered_us"] == 650.0
    assert t1["tie_out_error"] == 0.0
    assert t1["gap_us"] == 350.0              # unattributed transport time
    assert report["tie_out_violations"] == []
    assert report["max_tie_out_error"] == 0.0


def test_stitch_counts_orphans_loudly(tmp_path):
    report = reqtrace.stitch_requests(_drill_dumps(tmp_path))
    # the span whose trace id has no req/wall envelope anywhere
    assert report["orphan_spans"] == 1
    assert report["orphan_traces"] == ["nobody-minted-me"]
    assert report["traces"]["nobody-minted-me"]["orphan"]


def test_tie_out_flags_spans_outside_envelope(tmp_path):
    """A decode span running 300us past the wall end is overflow — the
    tie-out names it instead of trusting the row."""
    router = _dump(10, 1000.0, [
        _ev("req/wall", 100, 1000, trace_id="t1", outcome="finished",
            uid=1)])
    replica = _dump(20, 1000.0005, [
        _ev("req/queue", 0, 100, trace_id="t1", uid=7),
        _ev("req/decode", 100, 800, trace_id="t1", uid=7)])  # ends +1400
    paths = [_write(tmp_path, "r.json", router),
             _write(tmp_path, "w.json", replica)]
    report = reqtrace.stitch_requests(paths)
    t1 = report["traces"]["t1"]
    # 900us of span time, only 600 fit inside [100, 1100] -> 30% overflow
    assert t1["tie_out_error"] == pytest.approx(0.3)
    assert report["tie_out_violations"] == ["t1"]
    # ... and the CLI turns that into the regression exit code
    assert reqtrace.main(paths) == reqtrace.EXIT_REGRESSION


def test_unaligned_dump_is_flagged_not_dropped(tmp_path):
    router = _dump(10, 1000.0, [
        _ev("req/wall", 100, 1000, trace_id="t1", uid=1,
            outcome="finished")])
    headerless = {"traceEvents": [
        _ev("req/decode", 300, 200, trace_id="t1", uid=7)]}
    paths = [_write(tmp_path, "r.json", router),
             _write(tmp_path, "old.json", headerless)]
    report = reqtrace.stitch_requests(paths)
    assert report["alignment"] == "partial"
    assert report["unaligned_sources"] == [1]
    t1 = report["traces"]["t1"]
    assert not t1["aligned"]
    # the span still joined by trace id — flagged, not vanished
    assert any(s["name"] == "req/decode" for s in t1["spans"])


def test_cli_unreadable_and_artifact(tmp_path):
    assert reqtrace.main([str(tmp_path / "absent.json")]) \
        == reqtrace.EXIT_UNREADABLE
    paths = _drill_dumps(tmp_path)
    art = str(tmp_path / "reqtrace.json")
    assert reqtrace.main(paths + ["--out", art]) == reqtrace.EXIT_OK
    with open(art) as f:
        saved = json.load(f)
    assert saved["requests_stitched"] == 1
    assert saved["version"] == reqtrace.REQTRACE_VERSION


def test_render_mentions_the_story(tmp_path):
    report = reqtrace.stitch_requests(_drill_dumps(tmp_path))
    text = reqtrace.render(report)
    assert "1 requests stitched" in text
    assert "1 flight dumps" in text
    assert "t1" in text
    assert "flight" in text
    assert "nobody-minted-me" in text


def test_stage_registry_matches_stitcher_contract():
    """Every req/ span the stitcher understands is a registered trace
    name with a stage label; the envelope is not a stage."""
    from deepspeed_tpu.telemetry.names import TRACE_NAMES
    for name in REQ_STAGE_OF:
        assert name in TRACE_NAMES
    assert reqtrace.REQ_WALL_NAME in TRACE_NAMES
    assert reqtrace.REQ_WALL_NAME not in REQ_STAGE_OF


# ---------------------------------------------------------------------------
# env_report rows
# ---------------------------------------------------------------------------
def test_env_report_reqtrace_rows(tmp_path, monkeypatch):
    from deepspeed_tpu import env_report
    art = str(tmp_path / "reqtrace.json")
    assert reqtrace.main(_drill_dumps(tmp_path) + ["--out", art]) == 0
    monkeypatch.setenv(reqtrace.REQTRACE_ARTIFACT_ENV, art)
    rows = dict(env_report.reqtrace_report())
    assert "reqtrace" in rows
    assert "1 requests stitched" in rows["reqtrace"]
    assert "1 flight dumps" in rows["reqtrace"]
    assert "slo histograms" in rows
    assert "ttft" in rows["slo histograms"]
    assert "queue_wait" in rows["slo histograms"]


def test_env_report_reqtrace_hint_without_artifact(tmp_path, monkeypatch):
    from deepspeed_tpu import env_report
    monkeypatch.delenv(reqtrace.REQTRACE_ARTIFACT_ENV, raising=False)
    monkeypatch.chdir(tmp_path)       # no ./reqtrace.json here
    rows = dict(env_report.reqtrace_report())
    assert "no artifact" in rows["reqtrace"]
    assert reqtrace.REQTRACE_ARTIFACT_ENV in rows["reqtrace"]
