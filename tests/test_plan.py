"""``dstpu plan`` — step-time attribution / planning tests.

Contracts pinned here:

  golden       : the checked-in micro fixtures attribute to a ledger whose
                 stages (incl. residual) sum EXACTLY to each step window
                 and whose over-attribution (tie_out_error) stays within
                 the 5% clock-skew tolerance; proposals are deterministic
  synthetic    : a hand-built trace with known durations exercises every
                 stage (incl. ckpt + comm rollups) and the priority sweep's
                 nesting rules, to exact microseconds
  ratchet      : plan_baseline.json regression/stale detection follows the
                 dslint idiom — the checked-in baseline is clean against
                 the checked-in fixture, a seeded drain growth exits 1,
                 improvements surface as stale entries
  CLI          : exit-code matrix 0 ok / 1 regression / 2 unreadable, via
                 both attribution.main and the bin/dstpu subcommand
  quantiles    : Tracer.summary / prometheus_lines p50/p95/p99 to exact
                 values (attribution consumes the same quantile rule)
  slicing      : dstpu_trace --step-range / --track produce plan-loadable
                 slices that keep the sliced steps' drain/h2d/comm spans
  offline-only : no registered hot-path file can import the attribution
                 module, and the module itself never touches jax
  loop         : Autotuner(plan=...) executes ONLY the plan's proposals
                 and verifies the readback-transfer prediction by exact
                 span counting (the telemetry->plan->config acceptance)
  live         : a real micro training run under DSTPU_TRACE attributes
                 end to end
"""

import ast
import json
import math
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.telemetry import attribution
from deepspeed_tpu.telemetry import report as trace_report
from deepspeed_tpu.telemetry.tracer import Tracer

pytestmark = pytest.mark.plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "plan_fixtures")
SYNC_TRACE = os.path.join(FIXTURES, "micro_sync_trace.json")
ASYNC_TRACE = os.path.join(FIXTURES, "micro_async_trace.json")
BASELINE = os.path.join(REPO, attribution.PLAN_BASELINE_NAME)


def _stage_sum_us(window):
    return sum(window["stages_us"].values())


# ---------------------------------------------------------------------------
# golden attribution on the checked-in fixtures
# ---------------------------------------------------------------------------
def test_golden_sync_fixture_ledger_ties_out():
    rep = attribution.analyze_path(SYNC_TRACE)
    assert rep["mode"] == "sync"
    assert len(rep["windows"]) == 1
    w = rep["windows"][0]
    assert w["steps"] == 8
    # exclusive stages + residual sum EXACTLY to the window (residual is
    # the remainder by construction; rounding is 3 decimals of a us)
    assert _stage_sum_us(w) == pytest.approx(w["dur_us"], abs=0.01)
    # over-attribution stays within the acceptance tolerance
    assert w["tie_out_error"] <= attribution.TIE_OUT_TOLERANCE
    # per-step readback makes dispatch the dominant attributed stage
    agg = rep["aggregate"]
    assert agg["dispatch"]["share"] > agg["h2d"]["share"] > 0
    assert agg["drain"]["share"] == 0.0          # sync mode: no drain spans
    shares = sum(agg[s]["share"] for s in attribution.STAGES)
    assert shares == pytest.approx(1.0, abs=0.01)


def test_golden_sync_fixture_proposals_deterministic():
    rep1 = attribution.analyze_path(SYNC_TRACE)
    rep2 = attribution.analyze_path(SYNC_TRACE)
    assert rep1 == rep2                          # replay is a pure function
    props = rep1["proposals"]
    assert props[0]["id"] == "enable_async_pipeline"
    pred = props[0]["predicted"]
    assert pred["metric"] == "readback_transfers"
    assert pred["current"] == 8                  # per-step readback today
    assert pred["proposed"] == math.ceil(8 / pred["sync_every"])
    assert props[0]["overrides"]["async_pipeline"]["enabled"] is True
    # rule table orders by share, ties by id — stable across runs
    assert [p["id"] for p in props] == \
        sorted([p["id"] for p in props],
               key=lambda i: next(-p["share"] for p in props
                                  if p["id"] == i))


def test_golden_async_fixture_windows_and_config():
    rep = attribution.analyze_path(ASYNC_TRACE)
    assert rep["mode"] == "async"
    assert len(rep["windows"]) == 3              # 12 steps at sync_every=4
    for w in rep["windows"]:
        assert w["steps"] == 4
        assert _stage_sum_us(w) == pytest.approx(w["dur_us"], abs=0.01)
        assert w["tie_out_error"] <= attribution.TIE_OUT_TOLERANCE
        assert w["stages_us"]["drain"] > 0       # each window drains once
    cfg = rep["config_observed"]
    assert cfg["sync_every"] == 4                # read from the trace itself
    assert cfg["prefetch"] is False
    assert rep["steps_total"] == 12


def test_async_fixture_clean_against_checked_in_baseline():
    """fixtures + plan_baseline.json are ONE artifact set: the checked-in
    baseline must be exactly clean (no regressions, no stale entries)
    against the checked-in async fixture it was generated from."""
    rep = attribution.analyze_path(ASYNC_TRACE)
    baseline = attribution.load_plan_baseline(BASELINE)
    regressions, stale = attribution.check_baseline(rep, baseline)
    assert regressions == []
    assert stale == []
    assert set(baseline["entries"]) == set(attribution.STAGES)


# ---------------------------------------------------------------------------
# synthetic full-ledger golden (exact microseconds, every stage incl. ckpt)
# ---------------------------------------------------------------------------
def _ev(name, ts, dur, tid=1, cat="train", ph="X", **args):
    return {"name": name, "cat": cat, "ph": ph, "ts": ts, "dur": dur,
            "tid": tid, "args": args}


SYNTHETIC = {"traceEvents": [
    {"name": "thread_name", "ph": "M", "tid": 1,
     "args": {"name": "MainThread"}},
    {"name": "thread_name", "ph": "M", "tid": 2,
     "args": {"name": "prefetch"}},
    _ev("engine/steps_reconciled", 0, 10_000, steps=2, last_step=2),
    _ev("engine/dispatch", 0, 2_000, step=1),
    _ev("comm/h2d", 500, 500, cat="comm", bytes=4096),   # nested: h2d wins
    _ev("comm/all_reduce", 3_000, 400, cat="comm", bytes=1 << 20, world=8,
        algbw_gbps=2.0, busbw_gbps=3.5),
    _ev("comm/all_reduce", 3_500, 0, ph="i", cat="comm", bytes=1 << 20,
        world=8),                                        # in-jit analytic
    _ev("engine/dispatch", 5_000, 2_000, step=2),
    _ev("engine/drain", 7_000, 500, steps=2),
    _ev("ckpt/save", 7_600, 1_000, tag="t"),
    _ev("engine/drain", 8_000, 200),                     # nested: drain wins
    _ev("prefetch/next", 9_000, 100),                    # main-track stall
    _ev("prefetch/stage", 1_000, 1_000, tid=2),          # overlapped only
]}


def test_synthetic_exclusive_sweep_exact():
    rep = attribution.attribute(
        attribution.events_from_chrome(SYNTHETIC), source="synthetic")
    assert rep["mode"] == "async"
    (w,) = rep["windows"]
    st = w["stages_us"]
    assert st["h2d"] == 500                       # carved out of dispatch
    assert st["dispatch"] == 3_500                # 4000 - nested h2d
    assert st["comm"] == 400
    assert st["drain"] == 700                     # 500 + 200 inside ckpt
    assert st["ckpt"] == 800                      # 1000 - nested drain
    assert st["prefetch"] == 100                  # main-track stall only
    assert st["residual"] == 4_000
    assert _stage_sum_us(w) == w["dur_us"] == 10_000
    assert w["tie_out_error"] == 0.0
    # the worker's staging is informational overlap, never step cost
    assert w["overlapped_us"] == {"prefetch": 1_000.0}


def test_synthetic_comm_rollup_and_ckpt_proposal():
    rep = attribution.attribute(
        attribution.events_from_chrome(SYNTHETIC), source="synthetic")
    roll = rep["comm"]
    assert list(roll) == ["all_reduce@8"]
    r = roll["all_reduce@8"]
    assert r["count"] == 2                        # timed span + in-jit instant
    assert r["bytes"] == 2 << 20
    assert r["algbw_gbps_mean"] == pytest.approx(2.0)
    assert r["busbw_gbps_mean"] == pytest.approx(3.5)
    # ckpt is 8% — below its floor; grow it and the rule fires
    grown = json.loads(json.dumps(SYNTHETIC))
    for e in grown["traceEvents"]:
        if e["name"] == "ckpt/save":
            e["dur"] = 2_500
    rep2 = attribution.attribute(attribution.events_from_chrome(grown))
    assert any(p["id"] == "relax_ckpt_cadence" for p in rep2["proposals"])


def test_sync_pause_splits_windows():
    """A big inter-dispatch gap (eval phase, pause between loops) starts a
    NEW sync window — the idle time must never inflate any window's
    residual or the per-step quantiles the baseline ratchets."""
    ev = [_ev("engine/dispatch", t, 600, step=i + 1)
          for i, t in enumerate((0, 1_000, 2_000))]
    ev += [_ev("engine/dispatch", 500_000 + t, 600, step=i + 4)
           for i, t in enumerate((0, 1_000, 2_000))]
    rep = attribution.attribute(attribution.events_from_chrome(ev))
    assert len(rep["windows"]) == 2
    for w in rep["windows"]:
        assert w["steps"] == 3
        assert w["dur_us"] == 2_600                # pause excluded
        assert w["stages_us"]["residual"] == 800   # only the loop gaps
    assert rep["windows"][1]["last_step"] == 6


def test_sync_window_synthesis_without_reconciled_spans():
    """Sync traces have no reconciled spans: contiguous dispatch runs
    synthesize ONE window first-start -> last-end (inter-step host work
    still attributes)."""
    ev = [_ev("engine/dispatch", i * 1_000, 600, step=i + 1)
          for i in range(4)]
    rep = attribution.attribute(attribution.events_from_chrome(ev))
    (w,) = rep["windows"]
    assert rep["mode"] == "sync"
    assert w["steps"] == 4
    assert w["dur_us"] == 3_600
    assert w["stages_us"]["dispatch"] == 2_400
    assert w["stages_us"]["residual"] == 1_200    # the inter-dispatch gaps


def test_unreadable_traces_raise_plan_error():
    with pytest.raises(attribution.PlanError):
        attribution.events_from_chrome({"no": "traceEvents"})
    with pytest.raises(attribution.PlanError):
        attribution.events_from_chrome("not a trace")
    with pytest.raises(attribution.PlanError):
        attribution.attribute(attribution.events_from_chrome(
            {"traceEvents": [_ev("serve/engine_step", 0, 10)]}))


# ---------------------------------------------------------------------------
# regression ledger (ratchet idiom)
# ---------------------------------------------------------------------------
def _seed_drain_regression(factor=5):
    """Grow every drain span INTO its window (earlier start, same end, so
    clipping can't bound the growth away) — the deterministic 'drain time
    grew Nx' tripwire the baseline must flag."""
    with open(ASYNC_TRACE) as f:
        obj = json.load(f)
    for e in obj["traceEvents"]:
        if e.get("name") == "engine/drain":
            e["ts"] -= e["dur"] * (factor - 1)
            e["dur"] *= factor
    return obj


def test_seeded_drain_regression_detected(tmp_path):
    bad = tmp_path / "regressed.json"
    bad.write_text(json.dumps(_seed_drain_regression()))
    rep = attribution.analyze_path(str(bad))
    regressions, _ = attribution.check_baseline(
        rep, attribution.load_plan_baseline(BASELINE))
    assert any(r["stage"] == "drain" for r in regressions)
    ratio = next(r["ratio"] for r in regressions if r["stage"] == "drain")
    assert ratio > 2.0


def test_improvement_surfaces_as_stale_entry(tmp_path):
    """The other ratchet direction: a baseline recorded from a WORSE run
    goes stale once the stage improves — it must be expired explicitly
    (--write-baseline), never silently shield a future regression."""
    rep_bad = attribution.analyze_path(str(_write(tmp_path, "bad.json",
                                                  _seed_drain_regression())))
    bl_path = tmp_path / "baseline.json"
    attribution.write_plan_baseline(str(bl_path), rep_bad)
    rep_good = attribution.analyze_path(ASYNC_TRACE)
    regressions, stale = attribution.check_baseline(
        rep_good, attribution.load_plan_baseline(str(bl_path)))
    assert regressions == []
    assert any(r["stage"] == "drain" for r in stale)


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return p


# ---------------------------------------------------------------------------
# CLI exit-code matrix
# ---------------------------------------------------------------------------
def test_cli_exit_0_clean(capsys):
    rc = attribution.main([ASYNC_TRACE, "--baseline", BASELINE])
    assert rc == attribution.EXIT_OK
    out = capsys.readouterr().out
    assert "proposals" in out and "tie-out" in out


def test_cli_exit_1_regression(tmp_path, capsys):
    bad = _write(tmp_path, "regressed.json", _seed_drain_regression())
    rc = attribution.main([str(bad), "--baseline", BASELINE])
    assert rc == attribution.EXIT_REGRESSION
    err = capsys.readouterr().err
    assert "REGRESSION" in err and "drain" in err


def test_cli_exit_2_unreadable(tmp_path, capsys):
    garbage = tmp_path / "garbage.json"
    garbage.write_text("not json {")
    assert attribution.main([str(garbage)]) == attribution.EXIT_UNREADABLE
    nostep = _write(tmp_path, "nostep.json",
                    {"traceEvents": [_ev("serve/engine_step", 0, 10)]})
    assert attribution.main([str(nostep)]) == attribution.EXIT_UNREADABLE
    assert attribution.main([str(tmp_path / "absent.json")]) \
        == attribution.EXIT_UNREADABLE
    capsys.readouterr()


def test_cli_tolerance_overrides_baseline_factor(tmp_path, capsys):
    """--tolerance applies to the CHECK, not just baseline writing: the
    same seeded regression passes once the factor is raised past it."""
    bad = _write(tmp_path, "regressed.json", _seed_drain_regression())
    assert attribution.main([str(bad), "--baseline", BASELINE]) == 1
    assert attribution.main([str(bad), "--baseline", BASELINE,
                             "--tolerance", "50"]) == 0
    capsys.readouterr()


def test_cli_no_baseline_discovery_outside_trace_tree(tmp_path, capsys,
                                                      monkeypatch):
    """Discovery anchors at the TRACE path only: a trace outside the repo
    is a different workload — comparing it against the checked-in fixture
    baseline would flag meaningless regressions (cwd must not leak in)."""
    import shutil
    monkeypatch.chdir(REPO)                       # repo baseline in cwd
    loose = tmp_path / "loose_trace.json"
    shutil.copy(ASYNC_TRACE, loose)
    rc = attribution.main([str(loose), "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["baseline"]["path"] is None


def test_discovered_baseline_guarded_by_workload(tmp_path, capsys):
    """A DISCOVERED baseline only judges traces of its own workload: a
    real run's trace saved next to the fixture baseline must not be
    compared against micro-fixture quantiles (explicit --baseline always
    compares)."""
    import shutil
    shutil.copy(BASELINE, tmp_path / attribution.PLAN_BASELINE_NAME)
    other = tmp_path / "trace.json"           # same events, other workload
    other.write_text(json.dumps(_seed_drain_regression()))
    rc = attribution.main([str(other), "--json"])
    assert rc == 0                            # discovered: skipped, no lie
    assert json.loads(capsys.readouterr().out)["baseline"]["path"] is None
    same = tmp_path / "micro_async_trace.json"
    same.write_text(other.read_text())        # matching workload: compared
    assert attribution.main([str(same)]) == attribution.EXIT_REGRESSION
    capsys.readouterr()


def test_write_baseline_never_clobbers_other_workload(tmp_path, capsys):
    """--write-baseline on a DISCOVERED baseline of another workload
    starts a new baseline next to the trace (or refuses when that IS the
    conflicting location) — the checked-in fixture artifact set can't be
    silently overwritten by ratcheting an unrelated run."""
    import shutil
    nested = tmp_path / "runs"
    nested.mkdir()
    shutil.copy(BASELINE, tmp_path / attribution.PLAN_BASELINE_NAME)
    trace = nested / "mytrain.json"
    shutil.copy(ASYNC_TRACE, trace)
    assert attribution.main([str(trace), "--write-baseline"]) == 0
    err = capsys.readouterr().err
    assert "instead" in err                       # redirected, with a note
    redirected = nested / attribution.PLAN_BASELINE_NAME
    assert attribution.load_plan_baseline(
        str(redirected))["workload"] == "mytrain.json"
    # fixture baseline untouched
    assert attribution.load_plan_baseline(
        str(tmp_path / attribution.PLAN_BASELINE_NAME))["workload"] \
        == "micro_async_trace.json"
    # same-dir conflict: nowhere safe to redirect -> refuse, write nothing
    trace2 = tmp_path / "other.json"
    shutil.copy(ASYNC_TRACE, trace2)
    before = (tmp_path / attribution.PLAN_BASELINE_NAME).read_text()
    assert attribution.main([str(trace2), "--write-baseline"]) == 0
    assert "refusing" in capsys.readouterr().err
    assert (tmp_path / attribution.PLAN_BASELINE_NAME).read_text() == before


def test_prefetch_depth_proposal_is_self_sufficient():
    """Every async_pipeline override must carry enabled/prefetch: propose()
    never trusts the config file, so an Autotuner executing the proposal
    against a sync base config must still run the pipelined engine."""
    rep = attribution.analyze_path(ASYNC_TRACE)
    agg = {s: dict(rep["aggregate"][s]) for s in attribution.STAGES}
    agg["prefetch"]["share"] = 0.5                # dominant prefetch stall
    doctored = dict(rep, aggregate=agg)
    props = {p["id"]: p for p in attribution.propose(doctored)}
    ov = props["raise_prefetch_depth"]["overrides"]["async_pipeline"]
    assert ov["enabled"] is True and ov["prefetch"] is True


def test_write_baseline_preserves_stored_tolerance(tmp_path, capsys):
    bl = tmp_path / "bl.json"
    assert attribution.main([ASYNC_TRACE, "--baseline", str(bl),
                             "--write-baseline", "--tolerance", "3"]) == 0
    assert attribution.load_plan_baseline(str(bl))["tolerance"] == 3.0
    # ratchet rewrite without --tolerance keeps the factor the team chose
    assert attribution.main([ASYNC_TRACE, "--baseline", str(bl),
                             "--write-baseline"]) == 0
    assert attribution.load_plan_baseline(str(bl))["tolerance"] == 3.0
    capsys.readouterr()


def test_cli_artifact_json_and_write_baseline(tmp_path, capsys):
    out = tmp_path / "plan.json"
    bl = tmp_path / "bl.json"
    rc = attribution.main([ASYNC_TRACE, "--baseline", str(bl),
                           "--write-baseline", "--out", str(out), "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert json.loads(out.read_text()) == report
    assert report["baseline"]["path"] == str(bl)
    # the freshly written baseline is clean against its own report
    assert attribution.main([ASYNC_TRACE, "--baseline", str(bl)]) == 0
    capsys.readouterr()


def test_bin_dstpu_plan_subcommand():
    """The launcher CLI routes `plan` to the analyzer (and stays a
    checkout-runnable script)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "dstpu"), "plan",
         ASYNC_TRACE, "--baseline", BASELINE],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert "dstpu plan" in proc.stdout


# ---------------------------------------------------------------------------
# tracer quantiles (satellite: summary + prometheus_lines p50/p95/p99)
# ---------------------------------------------------------------------------
def test_summary_quantiles_exact_values():
    t = Tracer(capacity=256)
    t.configure(enabled=True)
    for ms in range(1, 21):                      # 1..20 ms, known spread
        t.complete("q/span", ms / 1000.0, end_ts=100.0 + ms)
    s = t.summary()["q/span"]
    # repo-wide rule: sorted[min(int(q*n), n-1)] over n=20 samples
    assert s["count"] == 20
    assert s["p50_s"] == pytest.approx(0.011)    # index 10
    assert s["p95_s"] == pytest.approx(0.020)    # index 19
    assert s["p99_s"] == pytest.approx(0.020)    # index 19
    assert s["max_s"] == pytest.approx(0.020)
    assert s["total_s"] == pytest.approx(sum(range(1, 21)) / 1000.0)


def test_prometheus_lines_carry_p95():
    t = Tracer(capacity=64)
    t.configure(enabled=True)
    for ms in (1, 2, 3, 4):
        t.complete("engine/drain", ms / 1000.0, end_ts=10.0 + ms)
    lines = t.prometheus_lines()
    for q, val in (("0.5", 0.003), ("0.95", 0.004), ("0.99", 0.004)):
        row = next(l for l in lines
                   if f'quantile="{q}"' in l and "engine/drain" in l)
        assert float(row.split()[-1]) == pytest.approx(val)


def test_attribution_quantile_rule_matches_tracer():
    from deepspeed_tpu.telemetry.tracer import _quantile
    vals = [float(v) for v in range(1, 21)]
    for q in (0.5, 0.95, 0.99):
        assert attribution.quantile(vals, q) == _quantile(vals, q)
    assert attribution.quantile([], 0.5) == 0.0


# ---------------------------------------------------------------------------
# dstpu_trace slicing (satellite: --step-range / --track)
# ---------------------------------------------------------------------------
def test_step_range_slice_keeps_window_spans(tmp_path, capsys):
    events = trace_report.load_events(ASYNC_TRACE)
    sliced = trace_report.filter_step_range(events, "6:9")
    steps = {int(e["args"]["step"]) for e in sliced
             if e.get("ph") == "X" and e.get("name") == "engine/dispatch"}
    assert steps >= {6, 7, 8, 9}                 # the requested steps...
    assert steps <= {5, 6, 7, 8, 9}              # ...plus at most the
    # window-anchor step the reconciled extension legitimately pulls in
    names = {e.get("name") for e in sliced}
    # the sliced steps' drain/h2d spans ride along even though they carry
    # no per-step arg — that is the point of wall-time slicing
    assert {"engine/drain", "comm/h2d", "engine/steps_reconciled"} <= names
    assert any(e.get("ph") == "M" for e in sliced)   # labels preserved
    # a slice is itself a plan-loadable trace
    out = tmp_path / "slice.json"
    rc = trace_report.main([ASYNC_TRACE, "--step-range", "6:9",
                            "--out", str(out), "--json"])
    assert rc == 0
    capsys.readouterr()
    rep = attribution.analyze_path(str(out))
    assert rep["steps_total"] == 8               # the two touched windows
    assert all(w["tie_out_error"] <= attribution.TIE_OUT_TOLERANCE
               for w in rep["windows"])


def test_track_filter_and_bad_specs(capsys):
    events = trace_report.load_events(ASYNC_TRACE)
    main_only = trace_report.filter_track(events, "MainThread")
    tids = {e.get("tid") for e in main_only if e.get("ph") != "M"}
    assert len(tids) == 1
    with pytest.raises(ValueError, match="MainThread"):
        trace_report.filter_track(events, "no-such-track")
    assert trace_report.main([ASYNC_TRACE, "--track", "nope"]) == 2
    assert trace_report.main([ASYNC_TRACE, "--step-range", "bogus"]) == 2
    assert trace_report.main([ASYNC_TRACE, "--step-range", "900:901"]) == 2
    assert trace_report.main([ASYNC_TRACE, "--track", "MainThread"]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# env_report row (satellite)
# ---------------------------------------------------------------------------
def test_env_report_plan_rows(tmp_path, monkeypatch, capsys):
    from deepspeed_tpu.env_report import plan_report
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv(attribution.PLAN_ARTIFACT_ENV, raising=False)
    rows = dict(plan_report())
    assert "no artifact" in rows["dstpu plan"]
    assert "ratcheted" in rows["plan baseline"]   # repo baseline discovered
    # produce an artifact, point the env var at it
    out = tmp_path / "plan.json"
    assert attribution.main([ASYNC_TRACE, "--baseline", BASELINE,
                             "--out", str(out)]) == 0
    capsys.readouterr()
    monkeypatch.setenv(attribution.PLAN_ARTIFACT_ENV, str(out))
    rows = dict(plan_report())
    assert str(out) in rows["dstpu plan"]
    assert "% of step time" in rows["dstpu plan"]
    n_stages = len(attribution.load_plan_baseline(BASELINE)["entries"])
    assert f"{n_stages} stages ratcheted" in rows["plan baseline"]


# ---------------------------------------------------------------------------
# offline-only contract (satellite: hotpath registry)
# ---------------------------------------------------------------------------
def _imports_of(path):
    tree = ast.parse(open(path).read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            mods.add(node.module)
    return mods


def test_plan_subcommand_never_imports_the_package():
    """`dstpu plan` file-loads the stdlib-only analyzer: the deepspeed_tpu
    package (and its jax import chain) must stay out of the process, so
    replaying a dump works on jax-less hosts and costs no framework
    import."""
    proc = subprocess.run(
        [sys.executable, "-X", "importtime",
         os.path.join(REPO, "bin", "dstpu"), "plan", ASYNC_TRACE, "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    imported = [l for l in proc.stderr.splitlines() if "import time:" in l]
    assert imported                                # importtime was active
    assert not any("deepspeed_tpu" in l for l in imported)


def test_telemetry_package_lazy_attribution_reexport():
    """The package __init__ re-exports the replay API lazily (PEP 562):
    hot-path files importing telemetry for get_tracer must not load the
    offline analyzer transitively."""
    code = (
        "import sys\n"
        "import deepspeed_tpu.telemetry as T\n"
        "assert 'deepspeed_tpu.telemetry.attribution' not in sys.modules\n"
        "T.analyze_path\n"
        "assert 'deepspeed_tpu.telemetry.attribution' in sys.modules\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_attribution_is_offline_only():
    """Both directions of the purity contract are now the DS009 lint
    rule (transitive module-level import graph, not just direct imports)
    — this test pins the declaration AND runs the real rule over the
    package. One subprocess keep-alive remains above
    (``test_plan_subcommand_never_imports_the_package``); the other
    scattered ``-X importtime`` checks collapsed into this rule."""
    from deepspeed_tpu.tools.dslint import lint_paths
    from deepspeed_tpu.tools.dslint.hotpath import OFFLINE_ONLY_MODULES
    from deepspeed_tpu.tools.dslint.rules.ds009_offline_purity import \
        OfflinePurityRule
    assert "deepspeed_tpu/telemetry/attribution.py" in OFFLINE_ONLY_MODULES
    res = lint_paths([os.path.join(REPO, "deepspeed_tpu")], root=REPO,
                     rules=[OfflinePurityRule()])
    assert not res.findings, "\n".join(f.render() for f in res.findings)


# ---------------------------------------------------------------------------
# the closed loop: plan -> Autotuner executes + verifies (acceptance)
# ---------------------------------------------------------------------------
def test_autotuner_executes_and_verifies_plan(tmp_path):
    """The acceptance drill: the sync fixture's plan proposes the async
    pipeline; Autotuner(plan=...) runs ONLY that candidate set and proves
    the predicted transfer reduction by exact drain-span counting
    (8 steps at sync_every=8 -> exactly 1 readback transfer)."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.models.simple import SimpleModel, random_batch
    rep = attribution.analyze_path(SYNC_TRACE)
    base = {"train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "autotuning": {"results_dir": str(tmp_path)}}
    tuner = Autotuner(model=SimpleModel(hidden_dim=32), base_config=base,
                      example_batch=random_batch(8),
                      batch_fn=lambda bs: random_batch(int(bs)),
                      measure_steps=8, plan=rep)
    cfg, metrics = tuner.tune()
    by_id = {v["proposal"]: v for v in tuner.plan_verifications}
    v = by_id["enable_async_pipeline"]
    assert v["verdict"] == "verified", v
    assert v["observed"]["steps"] == 8
    assert v["observed"]["transfers"] == 1       # ceil(8/8), counted
    assert v["observed"]["transfers_without_plan"] == 8
    # only the plan's executable proposals ran — no blind grid search
    assert {e.name for e in tuner.records} == \
        {f"plan_{p['id']}" for p in rep["proposals"] if p["overrides"]}
    assert cfg is not None and "async_pipeline" in cfg
    # verifications persist next to the tuning results
    results = json.load(open(tmp_path / "autotuning_results.json"))
    assert results["plan"]["verifications"]
    # and the tracer is back off for everyone else
    from deepspeed_tpu.telemetry import get_tracer
    assert not get_tracer().enabled


def test_verify_counterfactual_uses_baseline_cadence():
    """transfers_without_plan is the counterfactual at the cadence the
    PLAN observed — ceil(steps/1) for sync mode, ceil(steps/cur) for
    raise_sync_every — over THIS experiment's step count."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner, Experiment
    proposal = {"id": "raise_sync_every",
                "predicted": {"metric": "readback_transfers",
                              "sync_every": 16, "baseline_sync_every": 8}}
    exp = Experiment("plan_raise_sync_every", {})
    exp.status = "done"
    exp.metrics = {"trace_dispatch_spans": 3.0, "trace_drain_spans": 1.0}
    v = Autotuner._verify_proposal(None, proposal, exp)
    assert v["verdict"] == "verified"            # ceil(3/16) == 1
    assert v["observed"]["transfers_without_plan"] == 1   # ceil(3/8), NOT 3


def test_autotuner_load_plan_accepts_trace_and_artifact(tmp_path):
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    rep = Autotuner._load_plan(SYNC_TRACE)       # raw dump: attributed here
    assert rep["proposals"]
    art = tmp_path / "plan.json"
    art.write_text(json.dumps(attribution.analyze_path(SYNC_TRACE)))
    rep2 = Autotuner._load_plan(str(art))        # plan artifact: as-is
    assert rep2["proposals"] == rep["proposals"]
    with pytest.raises(ValueError, match="proposals"):
        Autotuner._load_plan({"not": "a plan"})


# ---------------------------------------------------------------------------
# live round-trip: a micro training run under DSTPU_TRACE (acceptance)
# ---------------------------------------------------------------------------
_MICRO_RUN = """
import jax, jax.numpy as jnp
import deepspeed_tpu
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM, random_tokens
cfg = LlamaConfig(vocab_size=2048, hidden_size=64, intermediate_size=172,
                  num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=64,
                  dtype=jnp.float32)
engine, _, _, _ = deepspeed_tpu.initialize(
    model=LlamaForCausalLM(cfg),
    config={"train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
            "steps_per_print": 10 ** 9},
    example_batch=random_tokens(2, 64, vocab_size=2048))
for i in range(11):          # the per-step readback loop of a plain script
    loss = engine.train_batch(
        batch=random_tokens(8, 64, vocab_size=2048, seed=i, gas=1),
        stacked=True)
    float(jax.device_get(loss))
"""


def test_micro_run_trace_roundtrip(tmp_path):
    trace = tmp_path / "run_trace.json"
    env = dict(os.environ, DSTPU_TRACE=str(trace), JAX_PLATFORMS="cpu",
               XLA_FLAGS="")
    proc = subprocess.run([sys.executable, "-c", _MICRO_RUN], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rep = attribution.analyze_path(str(trace))
    assert rep["mode"] == "sync"                  # no pipeline configured
    assert rep["steps_total"] >= 10               # the training loop
    for w in rep["windows"]:
        assert _stage_sum_us(w) == pytest.approx(w["dur_us"], abs=0.01)
        assert w["tie_out_error"] <= attribution.TIE_OUT_TOLERANCE
    # the plan knows what to do about a per-step-readback loop
    assert any(p["id"] == "enable_async_pipeline" for p in rep["proposals"])
