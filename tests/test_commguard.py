"""commguard tests: timeout-bounded collectives, distributed health, and
the coordinated comm-fault recovery drill.

Every fault is deterministic (chaos comm knobs key off guarded-call
indices; heartbeat staleness is driven by explicit clocks), so this suite
runs in tier-1 by default (``chaos`` marker) and asserts exact behavior:

  - bounded ops   -> a wedged guarded op raises ``CommWedgeError`` inside
                     the deadline with the dstrace comm-span tail attached;
                     TRANSIENT init failures retry with backoff; FATAL and
                     auth failures never retry
  - membership    -> per-rank heartbeat files classify peers alive/lost;
                     chaos-silenced ranks go stale exactly like dead ones
  - stragglers    -> rank-relative duration outliers emit ``comm/straggler``
                     instants and bump the proof counter
  - recovery      -> the acceptance drill: injected wedge -> classified
                     error -> autosave -> relaunch resumes bit-identical to
                     an uninterrupted baseline; exit code 75 so the elastic
                     agent accounts the relaunch like a preemption (free)
"""

import json
import os
import time

import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.guard import (COMM_FAULT_EXIT_CODE, CommGuard,
                                      CommGuardConfig, CommInitError,
                                      CommOutcome, CommPeerLostError,
                                      CommWedgeError, bounded_init,
                                      classify_exception)
from deepspeed_tpu.models.simple import SimpleModel, random_batch
from deepspeed_tpu.resilience import (ChaosConfig, ChaosMonkey,
                                      FaultTolerantRunner, Heartbeat,
                                      MembershipView, ResilienceConfig,
                                      StragglerDetector,
                                      find_latest_committed)
from deepspeed_tpu.telemetry import get_tracer
from serving_fakes import ResidentEngine

pytestmark = pytest.mark.chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = {
    "train_batch_size": 8,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
}


@pytest.fixture
def tracing():
    """Enable the process tracer for one test, fully restored afterwards."""
    t = get_tracer()
    t.clear()
    t.detach_sink()
    t.configure(enabled=True)
    try:
        yield t
    finally:
        t.configure(enabled=False)
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


def _guard_cfg(tmp_path, **kw):
    kw.setdefault("op_deadline_s", 0.3)
    kw.setdefault("heartbeat_interval_s", 0.05)
    kw.setdefault("lost_after_s", 0.5)
    kw.setdefault("membership_dir", str(tmp_path / "members"))
    return kw


def _runner(engine, tmp_path, chaos=None):
    rc = ResilienceConfig(diagnostics_dir=str(tmp_path / "diag"),
                          autosave={"io_backoff_s": 0.01})
    return FaultTolerantRunner(engine, save_dir=str(tmp_path / "ckpt"),
                               config=rc, chaos=chaos)


def _batch_fn(step):
    return random_batch(8, seed=step)


def _write_peer(path, rank, age_s=0.0, beat=1):
    """Publish a rank file aged ``age_s`` — staleness is judged by the
    file's mtime (the store's single clock), so simulating a dead peer
    means backdating the file itself, not the embedded wall-clock ts."""
    path.write_text(json.dumps(
        {"rank": rank, "pid": 9, "ts": time.time() - age_s, "beat": beat}))
    if age_s:
        t = time.time() - age_s
        os.utime(path, (t, t))


# ---------------------------------------------------------------------------
# outcome classification
# ---------------------------------------------------------------------------
def test_classify_exception_classes():
    assert classify_exception(ConnectionRefusedError("refused")) \
        is CommOutcome.TRANSIENT
    assert classify_exception(RuntimeError("UNAVAILABLE: channel down")) \
        is CommOutcome.TRANSIENT
    assert classify_exception(TimeoutError("rendezvous timed out")) \
        is CommOutcome.TRANSIENT
    # auth is NEVER transient — retrying a revoked credential burns the
    # deadline for nothing (even when the transport also says "refused")
    assert classify_exception(
        RuntimeError("PERMISSION_DENIED: connection refused for principal")) \
        is CommOutcome.FATAL
    assert classify_exception(ValueError("bad mesh shape")) \
        is CommOutcome.FATAL


# ---------------------------------------------------------------------------
# bounded_init: deadline + backoff retry
# ---------------------------------------------------------------------------
def test_bounded_init_transient_retried_then_ok():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionRefusedError("coordinator not up yet")
        return "connected"

    assert bounded_init(flaky, name="t", deadline_s=5.0, retries=3,
                        backoff_s=0.01) == "connected"
    assert len(calls) == 3


def test_bounded_init_transient_budget_exhausted():
    def always_down():
        raise ConnectionResetError("reset by peer")

    with pytest.raises(CommInitError) as ei:
        bounded_init(always_down, name="t", deadline_s=5.0, retries=2,
                     backoff_s=0.01)
    assert ei.value.outcome is CommOutcome.TRANSIENT
    assert ei.value.attempts == 3          # 1 try + 2 retries
    assert isinstance(ei.value.__cause__, ConnectionResetError)


def test_bounded_init_fatal_never_retried():
    calls = []

    def fatal():
        calls.append(1)
        raise RuntimeError("permission denied: bad TPU credential")

    with pytest.raises(CommInitError) as ei:
        bounded_init(fatal, name="t", deadline_s=5.0, retries=5,
                     backoff_s=0.01)
    assert ei.value.outcome is CommOutcome.FATAL
    assert len(calls) == 1


def test_bounded_init_wedge_detected_within_deadline():
    t0 = time.monotonic()
    with pytest.raises(CommWedgeError) as ei:
        bounded_init(lambda: time.sleep(60), name="pjrt", deadline_s=0.2,
                     retries=3, backoff_s=0.01)
    assert time.monotonic() - t0 < 5.0     # detected, not sat out
    assert ei.value.outcome is CommOutcome.TIMEOUT
    assert ei.value.op == "pjrt"


def test_bounded_init_zero_deadline_runs_inline():
    assert bounded_init(lambda: 42, name="t", deadline_s=0) == 42


def test_init_distributed_wedge_proof(monkeypatch):
    """The BENCH r02–r05 wedge, mechanized: a hung rendezvous becomes a
    classified error inside the deadline; a transient one is retried."""
    import jax

    from deepspeed_tpu.comm.mesh import init_distributed

    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: time.sleep(60))
    t0 = time.monotonic()
    with pytest.raises(CommWedgeError):
        init_distributed(coordinator_address="127.0.0.1:1",
                         num_processes=2, process_id=0, deadline_s=0.2)
    assert time.monotonic() - t0 < 5.0

    calls = []

    def flaky(**kw):
        calls.append(kw)
        if len(calls) < 2:
            raise ConnectionRefusedError("coordinator not up yet")

    monkeypatch.setattr(jax.distributed, "initialize", flaky)
    init_distributed(coordinator_address="127.0.0.1:1", num_processes=2,
                     process_id=0, deadline_s=5.0, backoff_s=0.01)
    assert len(calls) == 2


# ---------------------------------------------------------------------------
# CommGuard: bounded eager ops + chaos faults
# ---------------------------------------------------------------------------
def test_guard_ok_op_counted_and_noted(tmp_path):
    guard = CommGuard(CommGuardConfig(enabled=True))
    noted = []
    from deepspeed_tpu.comm.guard import set_comm_op_listener
    set_comm_op_listener(noted.append)
    try:
        assert guard.run("scatter", lambda: "v") == "v"
    finally:
        set_comm_op_listener(None)
    assert guard.counters["ok"] == 1
    assert noted == ["scatter"]


def test_guard_chaos_wedge_raises_with_comm_tail(tracing):
    chaos = ChaosMonkey(ChaosConfig(seed=3, comm_wedge_call=1))
    guard = CommGuard(CommGuardConfig(enabled=True, op_deadline_s=0.2),
                      chaos=chaos)
    assert guard.run("allgather", lambda: 1) == 1      # call 0 unharmed
    t0 = time.monotonic()
    with pytest.raises(CommWedgeError) as ei:
        guard.run("allgather", lambda: 1)              # call 1 wedges
    assert time.monotonic() - t0 < 5.0
    assert guard.counters["timeout"] == 1
    assert chaos.injected["comm_wedge"] == 1
    # the error carries the dstrace comm tail: the completed call-0 span
    # and the wedge instant are both in it
    names = [e["name"] for e in ei.value.comm_tail]
    assert "comm/guarded/allgather" in names
    assert "comm/wedge" in names
    # a second wedge-eligible call is NOT re-wedged once DSTPU_RESUME is
    # set (comm_wedge_once spares the relaunched worker)
    os.environ["DSTPU_RESUME"] = "latest"
    try:
        chaos2 = ChaosMonkey(ChaosConfig(seed=3, comm_wedge_call=0))
        guard2 = CommGuard(CommGuardConfig(enabled=True, op_deadline_s=0.2),
                           chaos=chaos2)
        assert guard2.run("allgather", lambda: 1) == 1
        assert chaos2.injected["comm_wedge"] == 0
    finally:
        del os.environ["DSTPU_RESUME"]


def test_guard_chaos_delay_is_slow_but_ok():
    chaos = ChaosMonkey(ChaosConfig(seed=3, comm_delay_calls=frozenset({0}),
                                    comm_delay_s=0.05))
    guard = CommGuard(CommGuardConfig(enabled=True, op_deadline_s=5.0),
                      chaos=chaos)
    t0 = time.monotonic()
    assert guard.run("reduce", lambda: "r") == "r"
    assert time.monotonic() - t0 >= 0.05
    assert guard.counters["ok"] == 1
    assert chaos.injected["comm_delay"] == 1


def test_guard_failure_classified_and_reraised():
    guard = CommGuard(CommGuardConfig(enabled=True))
    with pytest.raises(ValueError):
        guard.run("scatter", lambda: (_ for _ in ()).throw(
            ValueError("shape mismatch")))
    assert guard.counters["fatal"] == 1


# ---------------------------------------------------------------------------
# membership: heartbeats + peer classification
# ---------------------------------------------------------------------------
def test_heartbeat_publishes_and_membership_sees_alive(tmp_path):
    d = str(tmp_path / "members")
    view = MembershipView(d, lost_after_s=5.0)
    with Heartbeat(0, d, interval_s=0.05, listen_comm_ops=False) as hb:
        hb.note_op("all_reduce")
        time.sleep(0.15)
        snap = view.snapshot()
    assert 0 in snap and snap[0].alive
    assert snap[0].beat >= 1
    # the published record carries the last-completed comm op
    final = view.snapshot()[0]
    assert final.last_op == "all_reduce"
    assert final.op_seq == 1
    assert view.healthy()


def test_membership_stale_peer_classified_lost(tmp_path):
    d = tmp_path / "members"
    d.mkdir()
    _write_peer(d / "rank_0.json", 0, beat=5)
    _write_peer(d / "rank_1.json", 1, age_s=60.0, beat=3)
    view = MembershipView(str(d), lost_after_s=5.0)
    assert view.lost_peers() == [1]
    assert not view.healthy()
    summary = view.summary()
    assert summary["lost"] == [1]
    assert summary["ranks"]["0"]["alive"] is True
    assert summary["ranks"]["1"]["alive"] is False


def test_membership_age_is_mtime_not_writer_clock(tmp_path):
    """A freshly-published heartbeat from a host whose wall clock is 60s
    behind must NOT read as lost — age comes from the rank file's mtime
    (the store's single clock), never the writer's embedded timestamp."""
    d = tmp_path / "members"
    d.mkdir()
    (d / "rank_0.json").write_text(json.dumps(
        {"rank": 0, "pid": 1, "ts": time.time() - 60.0, "beat": 7}))
    view = MembershipView(str(d), lost_after_s=5.0)
    snap = view.snapshot()
    assert snap[0].alive and snap[0].age_s < 5.0
    assert view.lost_peers() == []


def test_membership_expected_rank_missing_after_grace(tmp_path):
    d = tmp_path / "members"
    d.mkdir()
    (d / "rank_0.json").write_text(json.dumps(
        {"rank": 0, "pid": 1, "ts": time.time(), "beat": 1}))
    view = MembershipView(str(d), lost_after_s=0.1, expected_ranks=(0, 1))
    # inside the startup grace a never-published peer is NOT lost yet
    assert view.lost_peers() == []
    time.sleep(0.15)
    # keep rank 0 fresh — only the never-published rank 1 should be lost
    (d / "rank_0.json").write_text(json.dumps(
        {"rank": 0, "pid": 1, "ts": time.time(), "beat": 2}))
    assert view.lost_peers() == [1]


def test_chaos_silenced_heartbeat_goes_stale(tmp_path):
    d = str(tmp_path / "members")
    chaos = ChaosMonkey(ChaosConfig(seed=1, peer_dead_ranks=frozenset({1})))
    hb0 = Heartbeat(0, d, interval_s=0.05, chaos=chaos,
                    listen_comm_ops=False).start()
    hb1 = Heartbeat(1, d, interval_s=0.05, chaos=chaos,
                    listen_comm_ops=False).start()
    try:
        time.sleep(0.2)
        view = MembershipView(d, lost_after_s=5.0)
        snap = view.snapshot()
        assert 0 in snap                     # rank 0 publishes normally
        assert 1 not in snap                 # rank 1 silenced — never lands
        view2 = MembershipView(d, lost_after_s=0.0001, expected_ranks=(0, 1))
        time.sleep(0.01)
        assert 1 in view2.lost_peers()
    finally:
        hb0.stop()
        hb1.stop()


def test_heartbeat_overlap_keeps_newer_listener(tmp_path):
    """Stopping an OLD heartbeat must not sever a newer one's comm-op feed
    (rolling runner replacement / training + serving in one process)."""
    from deepspeed_tpu.comm.guard import note_comm_op
    d = str(tmp_path / "members")
    old = Heartbeat(0, d, interval_s=0.05).start()
    new = Heartbeat(0, d, interval_s=0.05).start()   # takes the listener
    try:
        old.stop()                                   # must NOT clear it
        note_comm_op("all_reduce")
        with new._lock:
            assert new._last_op == "all_reduce"
            assert new._op_seq == 1
    finally:
        new.stop()
    # the newest heartbeat's own stop DOES clear its listener
    note_comm_op("all_gather")
    with new._lock:
        assert new._op_seq == 1


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------
def test_straggler_outlier_flagged_with_instant(tracing):
    det = StragglerDetector(factor=3.0)
    out = det.observe("all_reduce", {0: 0.010, 1: 0.011, 2: 0.012, 3: 0.500})
    assert out == [3]
    assert det.count == 1
    assert det.flagged[0][0] == "all_reduce" and det.flagged[0][1] == 3
    assert tracing.instant_counts().get("comm/straggler") == 1


def test_straggler_uniform_ranks_not_flagged():
    det = StragglerDetector(factor=3.0)
    assert det.observe("all_reduce", {0: 0.01, 1: 0.012, 2: 0.011}) == []
    assert det.count == 0


def test_straggler_min_s_filters_clock_noise():
    det = StragglerDetector(factor=3.0, min_s=1.0)
    # 5x the median but only 40ms over it — below the absolute floor
    assert det.observe("barrier", {0: 0.01, 1: 0.01, 2: 0.05}) == []
    assert det.count == 0


def test_straggler_ingest_synthetic_spans(tracing):
    """The satellite proof: straggler instants from synthetic span timings
    shaped like ``Tracer.events_snapshot`` rows."""
    #              (eid, name, cat, ph, ts, dur, tid, args)
    events = [
        (1, "comm/all_gather", "comm", "X", 0.0, 0.010, 0, {"rank": 0}),
        (2, "comm/all_gather", "comm", "X", 0.0, 0.012, 0, {"rank": 1}),
        (3, "comm/all_gather", "comm", "X", 0.0, 0.011, 0, {"rank": 2}),
        (4, "comm/all_gather", "comm", "X", 0.0, 0.900, 0, {"rank": 3}),
        # non-span / non-comm / rank-less rows must be ignored
        (5, "comm/all_gather", "comm", "i", 0.0, 0.0, 0, {"rank": 0}),
        (6, "engine/dispatch", "host", "X", 0.0, 9.9, 0, {"rank": 0}),
        (7, "comm/all_gather", "comm", "X", 0.0, 9.9, 0, {}),
    ]
    det = StragglerDetector(factor=3.0)
    assert det.ingest_spans(events) == [3]
    assert det.count == 1
    assert tracing.instant_counts().get("comm/straggler") == 1


def test_runner_feeds_stragglers_from_config(tmp_path, tracing):
    """The ``straggler_*`` config keys are live: the runner constructs the
    detector from the group and judges fresh rank-tagged comm spans at the
    membership-poll cadence — a 2.5x outlier is flagged at factor 2.0 (it
    would NOT be at the default 3.0), and already-judged event ids are
    never double-counted."""
    engine = _engine(seed=1, extra={"comm_guard": _guard_cfg(
        tmp_path, straggler_factor=2.0)})
    runner = _runner(engine, tmp_path)
    try:
        assert runner.straggler is not None
        assert runner.straggler.factor == 2.0
        for rank, dur in ((0, 0.10), (1, 0.11), (2, 0.12), (3, 0.27)):
            tracing.complete("comm/all_gather", dur, cat="comm", rank=rank)
        runner._check_peers()
        assert runner.straggler.count == 1
        assert runner.straggler.flagged[0][1] == 3
        # second poll over the SAME spans: no double count
        runner.membership._next_poll = 0.0
        runner._check_peers()
        assert runner.straggler.count == 1
    finally:
        runner.close()


# ---------------------------------------------------------------------------
# the acceptance drill: wedge -> classified error -> autosave -> resume
# ---------------------------------------------------------------------------
def _trajectory(engine, start, stop):
    out = []
    for step in range(start, stop):
        loss = float(engine.train_batch(batch=_batch_fn(step)))
        out.append((loss, engine.get_lr()[0]))
    return out


def test_comm_wedge_drill_autosave_then_resume_matches_baseline(
        tmp_path, tracing):
    """The acceptance scenario: an injected comm wedge is detected within
    the configured deadline (no hang), produces a classified error with the
    dstrace comm-span tail attached, autosaves, and a relaunched run
    resumes bit-identical to an uninterrupted baseline."""
    total = 6
    base = _engine(seed=1)
    base_traj = _trajectory(base, 0, total)

    victim = _engine(seed=1, extra={"comm_guard": _guard_cfg(tmp_path)})
    chaos = ChaosMonkey(ChaosConfig(seed=7, comm_wedge_call=3))
    runner = _runner(victim, tmp_path, chaos=chaos)
    assert runner.comm_guard is not None and runner.heartbeat is not None
    # the runner installed its guard process-wide: the comm facade's eager
    # ops (device_broadcast, ckpt scatter) route through it with NO caller
    # change — the drill below never references runner.comm_guard
    from deepspeed_tpu.comm.guard import get_active_guard, guarded
    assert get_active_guard() is runner.comm_guard

    def guarded_batches(step):
        # the eager guarded op an UNMODIFIED training script would run
        # (ckpt scatter, debug broadcast, ... — routed via the active
        # guard exactly like comm.device_broadcast) — call #3 wedges,
        # i.e. during step 3
        guarded("ckpt_scatter", lambda: None)
        return _batch_fn(step)

    t0 = time.monotonic()
    result = runner.run(num_steps=total, batch_fn=guarded_batches)
    detect_s = time.monotonic() - t0
    runner.close()
    # detected within the deadline (0.3s) + slack, never a hang
    assert result.stop_reason == "comm_fault"
    assert result.steps_completed == 3
    assert result.preempted                      # relaunch-with-resume class
    assert result.exit_code == COMM_FAULT_EXIT_CODE
    assert chaos.injected["comm_wedge"] == 1
    assert runner.comm_guard.counters["timeout"] == 1
    assert detect_s < 60.0                       # vs the 0.3s deadline

    # autosave committed at the fault boundary
    assert find_latest_committed(str(tmp_path / "ckpt")) == "global_step3"
    # diagnostic bundle carries the classified fault + comm-span tail
    bundle = tmp_path / "diag" / "comm_fault_step3"
    with open(bundle / "diag.json") as f:
        diag = json.load(f)
    assert diag["reason"] == "comm_fault"
    assert diag["comm_fault"]["op"] == "ckpt_scatter"
    assert diag["comm_fault"]["outcome"] == "timeout"
    tail_names = [e["name"] for e in diag["comm_fault"]["comm_tail"]]
    assert "comm/wedge" in tail_names

    # --- relaunch: fresh process state, different init seed -------------
    resumed = _engine(seed=42, extra={"comm_guard": _guard_cfg(tmp_path)})
    runner2 = _runner(resumed, tmp_path)
    assert runner2.resume_from_latest() == "global_step3"
    assert resumed.global_steps == 3
    resumed_traj = _trajectory(resumed, 3, total)
    runner2.close()
    for (bl, blr), (rl, rlr) in zip(base_traj[3:], resumed_traj):
        assert abs(bl - rl) < 1e-6
        assert rlr == pytest.approx(blr, rel=1e-7)
    assert resumed.global_steps == total


def test_peer_loss_stops_run_with_comm_fault(tmp_path):
    """A peer whose heartbeat goes stale becomes CommPeerLostError at the
    step boundary — coordinated stop + autosave, never a wedged collective."""
    members = tmp_path / "members"
    members.mkdir()
    # a peer that published once, 60s ago, then died
    _write_peer(members / "rank_1.json", 1, age_s=60.0, beat=2)
    engine = _engine(seed=1, extra={"comm_guard": _guard_cfg(
        tmp_path, lost_after_s=0.5)})
    runner = _runner(engine, tmp_path)
    result = runner.run(num_steps=4, batch_fn=_batch_fn)
    runner.close()
    assert result.stop_reason == "comm_fault"
    assert result.steps_completed == 0           # detected before stepping
    assert result.exit_code == COMM_FAULT_EXIT_CODE
    assert find_latest_committed(str(tmp_path / "ckpt")) is not None
    with open(tmp_path / "diag" / "comm_fault_step0" / "diag.json") as f:
        diag = json.load(f)
    assert diag["comm_fault"]["op"] == "membership"


def test_runner_heartbeat_stops_on_close(tmp_path):
    from deepspeed_tpu.comm.guard import get_active_guard
    engine = _engine(seed=1, extra={"comm_guard": _guard_cfg(tmp_path)})
    runner = _runner(engine, tmp_path)
    hb_thread = runner.heartbeat._thread
    assert hb_thread.is_alive()
    assert get_active_guard() is runner.comm_guard
    runner.close()
    assert runner.heartbeat._thread is None
    assert not hb_thread.is_alive()
    assert get_active_guard() is None      # facade back to inline ops


def test_run_result_exit_code_classification():
    """The worker idiom ``sys.exit(result.exit_code)``: every stop reason
    maps into the elastic agent's accounting classes."""
    import signal as _signal
    from deepspeed_tpu.resilience.runner import RunResult
    assert RunResult(stop_reason="completed").exit_code == 0
    assert RunResult(stop_reason="comm_fault").exit_code == \
        COMM_FAULT_EXIT_CODE
    # preemption carries the 128+signal shell convention the agent's
    # preemption_exit_codes (143, 130) already recognizes
    assert RunResult(stop_reason="preempted",
                     preempt_signal=_signal.SIGTERM).exit_code == 143
    assert RunResult(stop_reason="preempted",
                     preempt_signal=_signal.SIGINT).exit_code == 130
    # watchdog/unknown-signal stops default to the SIGTERM form
    assert RunResult(stop_reason="watchdog").exit_code == 143
    from deepspeed_tpu.elasticity import WorkerSpec
    spec = WorkerSpec(cmd=["x"])
    assert 143 in spec.preemption_exit_codes
    assert 130 in spec.preemption_exit_codes
    assert COMM_FAULT_EXIT_CODE in spec.comm_fault_exit_codes


# ---------------------------------------------------------------------------
# elastic-agent accounting: comm faults are free, like preemptions
# ---------------------------------------------------------------------------
def test_agent_comm_fault_exit_is_free_not_budgeted():
    from deepspeed_tpu.elasticity import ElasticAgent, WorkerSpec
    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 64,
                          "micro_batch_sizes": [2], "min_gpus": 1,
                          "max_gpus": 8, "version": 0.1}}
    agent = ElasticAgent(WorkerSpec(cmd=["x"]), cfg,
                         popen=lambda *a, **k: None)
    agent._last_codes = [COMM_FAULT_EXIT_CODE]
    assert agent._is_comm_fault(COMM_FAULT_EXIT_CODE)
    assert not agent._is_preemption(COMM_FAULT_EXIT_CODE)
    # comm fault in one worker + clean preemption in another: still free
    agent._last_codes = [COMM_FAULT_EXIT_CODE, -15]
    assert agent._is_comm_fault(COMM_FAULT_EXIT_CODE)
    # comm fault alongside a real crash: the generation is a crash
    agent._last_codes = [COMM_FAULT_EXIT_CODE, 1]
    assert not agent._is_comm_fault(1)
    # pure preemption vector is not a comm fault (no 75 present)
    agent._last_codes = [-15, 143]
    assert not agent._is_comm_fault(143)


def test_agent_relaunches_comm_fault_without_consuming_budget():
    from deepspeed_tpu.elasticity import ElasticAgent, WorkerSpec
    codes = iter([COMM_FAULT_EXIT_CODE, 0])

    class _Proc:
        def __init__(self):
            self.code = next(codes)

        def poll(self):
            return self.code

        def terminate(self):
            pass

        def wait(self, timeout=None):
            return 0

        def kill(self):
            pass

    launches = []

    def popen(cmd, env=None):
        launches.append(env)
        return _Proc()

    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 64,
                          "micro_batch_sizes": [2], "min_gpus": 1,
                          "max_gpus": 8, "version": 0.1}}
    spec = WorkerSpec(cmd=["x"], max_restarts=0, monitor_interval_s=0.01,
                      restart_backoff_s=0.0)
    agent = ElasticAgent(spec, cfg, popen=popen)
    assert agent.run() == 0
    assert agent.crash_restarts == 0             # budget untouched
    assert len(launches) == 2
    assert launches[-1]["DSTPU_RESUME"] == "latest"


def test_agent_exports_init_budget_env_from_config():
    """The ``comm_guard.init_*`` keys are live end to end: the agent
    exports them as DSTPU_COMM_INIT_* so every (re)launched worker's
    ``init_distributed`` rendezvous honors the configured budget."""
    from deepspeed_tpu.comm.guard import (INIT_BACKOFF_ENV,
                                          INIT_DEADLINE_ENV,
                                          INIT_RETRIES_ENV)
    from deepspeed_tpu.elasticity import ElasticAgent, WorkerSpec
    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 64,
                          "micro_batch_sizes": [2], "min_gpus": 1,
                          "max_gpus": 8, "version": 0.1},
           "comm_guard": {"init_deadline_s": 30.0, "init_retries": 1,
                          "init_backoff_s": 0.5}}
    launches = []

    def popen(cmd, env=None):
        launches.append(env)

        class _Done:
            def poll(self):
                return 0
        return _Done()

    agent = ElasticAgent(
        WorkerSpec(cmd=["x"], monitor_interval_s=0.01,
                   env={INIT_RETRIES_ENV: "9"}),    # operator env wins
        cfg, popen=popen)
    assert agent.run() == 0
    env = launches[0]
    assert env[INIT_DEADLINE_ENV] == "30.0"
    assert env[INIT_BACKOFF_ENV] == "0.5"
    assert env[INIT_RETRIES_ENV] == "9"


# ---------------------------------------------------------------------------
# serving: membership view flips health to degraded
# ---------------------------------------------------------------------------
class _IdleEngine(ResidentEngine):
    """Minimal engine double that never has work — the membership poll on
    the serve tick is the thing under test."""

    def has_work(self):
        return False


def test_serving_degrades_on_lost_peer(tmp_path):
    from deepspeed_tpu.serving import ServingConfig
    from deepspeed_tpu.serving.server import InferenceServer

    members = tmp_path / "members"
    members.mkdir()
    _write_peer(members / "rank_1.json", 1, age_s=60.0, beat=2)
    view = MembershipView(str(members), lost_after_s=0.5)
    server = InferenceServer(_IdleEngine(), ServingConfig(idle_poll_s=0.001),
                             membership=view).start()
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            h = server.health()
            if h["status"] == "degraded":
                break
            time.sleep(0.01)
        h = server.health()
        assert h["status"] == "degraded", h
        assert "peer" in h["degraded_reason"]
        assert h["membership"]["lost"] == [1]
        assert h["membership"]["ranks"]["1"]["alive"] is False
    finally:
        server.stop(drain_timeout=2.0)


def test_serving_healthy_membership_reported_not_degraded(tmp_path):
    from deepspeed_tpu.serving import ServingConfig
    from deepspeed_tpu.serving.server import InferenceServer

    members = tmp_path / "members"
    members.mkdir()
    (members / "rank_0.json").write_text(json.dumps(
        {"rank": 0, "pid": 1, "ts": time.time(), "beat": 1}))

    view = MembershipView(str(members), lost_after_s=3600.0)
    server = InferenceServer(_IdleEngine(), ServingConfig(idle_poll_s=0.001),
                             membership=view).start()
    try:
        time.sleep(0.1)
        h = server.health()
        assert h["status"] == "serving", h
        assert h["membership"]["lost"] == []
    finally:
        server.stop(drain_timeout=2.0)
