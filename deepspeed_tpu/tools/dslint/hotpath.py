"""The hot-path declaration DS002 enforces: roots + escape hatches.

Until dslint v2 this file was a 300-line registry enumerating every
function on the per-step/per-tick fast path — every PR had to remember
to extend it, and a helper extracted out of a registered function
silently fell off the tripwire. DS002 is now **taint propagation** over
the project call graph (``tools/dslint/callgraph.py``): host-sync sinks
(``float()`` on arrays, ``.item()``, ``device_get``,
``block_until_ready``, ``np.asarray``) are findings in any function
*reachable from a registered hot root*, so new helpers are covered the
moment a hot path starts calling them. What remains here is the part
that genuinely is a reviewed declaration:

  HOT_ROOTS        the entry points INTO hot code: the training dispatch,
                   the serve tick, the router pick/poll, the planners,
                   and the bench/listener-facing surface whose callers
                   live outside the package (HTTP handlers, installed
                   callbacks, bench harnesses — edges no static call
                   graph can see)
  ESCAPE_HATCHES   the designed synchronous points: THE drain, the host
                   offload path, the guarded async fan-in
  OFFLINE_ONLY_MODULES  the inverse contract, enforced by DS009

``tests/test_dslint.py`` proves the taint closure of HOT_ROOTS covers a
strict superset of the retired registry, and that every root is
load-bearing (deleting any one loses coverage of at least one formerly
registered function).

Root fields:

  path / qualname  repo-relative file + dotted function name
  reason           why this is an entry point (shown in findings)
  forbidden        sink matchers for paths tainted from this root

Hatch fields:

  mode = "sync_ok"   the function's OWN body may sync (it IS the
                     designated sync point) but its callees are still
                     traversed — the drain's bookkeeping helpers stay
                     covered
  mode = "prune"     the whole subtree under the function is exempt and
                     not traversed (explicitly host-synchronous designs:
                     the streamed host optimizer step)
  mode = "guarded"   branch-sensitive: sinks on lines that provably
                     execute only when ``guard_attr`` is false (the
                     designed synchronous fallback) are exempt; the
                     async side and shared code stay covered
"""

import dataclasses
from typing import Tuple

#: calls that force (or can force) a device->host sync. ``float()`` on a
#: jax.Array blocks on the value; ``.item()``/``np.asarray``/``np.array``
#: copy to host; device_get / block_until_ready are explicit.
DEFAULT_FORBIDDEN: Tuple[str, ...] = (
    "float", ".item", ".device_get", ".block_until_ready",
    ".copy_to_host_async", "np.asarray", "np.array",
)

#: files whose hot code legitimately touches numpy on HOST arrays (batch
#: staging before H2D, the already-gathered page codec, healthz int
#: arithmetic) — ``np.asarray``/``np.array`` stay allowed there; device
#: syncs stay forbidden. This mirrors the retired registry's
#: ENGINE_FORBIDDEN profile, keyed by file instead of by spec.
HOST_NUMPY_FILES: Tuple[str, ...] = (
    "deepspeed_tpu/runtime/engine.py",
    "deepspeed_tpu/runtime/dataloader.py",
    "deepspeed_tpu/serving/server.py",
    "deepspeed_tpu/inference/v2/kv_offload.py",
    # host token tables: prompt ids arrive as python lists and are staged
    # into numpy before the single H2D
    "deepspeed_tpu/inference/v2/ragged_manager.py",
    # fault injection poisons the HOST batch before dispatch — that is
    # the drill (corrupting on device would change what the guard sees)
    "deepspeed_tpu/resilience/chaos.py",
)

#: the fleet router runs on a deviceless host by design (its roots'
#: ``reason`` says so): ``float()`` there parses JSON bodies and healthz
#: snapshots, never a device array. Explicit syncs stay forbidden — a
#: router importing jax readback APIs is wrong no matter the host.
ROUTER_FORBIDDEN: Tuple[str, ...] = tuple(
    m for m in DEFAULT_FORBIDDEN if m != "float")


@dataclasses.dataclass(frozen=True)
class HotRoot:
    path: str
    qualname: str
    reason: str
    forbidden: Tuple[str, ...] = DEFAULT_FORBIDDEN


@dataclasses.dataclass(frozen=True)
class EscapeHatch:
    path: str
    qualname: str
    mode: str                   # "sync_ok" | "prune" | "guarded"
    reason: str
    guard_attr: str = ""        # mode == "guarded" only


HOT_ROOTS: Tuple[HotRoot, ...] = (
    # -- dispatch roots: the loops themselves -------------------------------
    HotRoot(
        path="deepspeed_tpu/runtime/engine.py",
        qualname="DeepSpeedTPUEngine.train_batch",
        reason="the training dispatch: everything it reaches runs every "
               "step — one sync re-serializes the pipeline while every "
               "timing test keeps passing"),
    HotRoot(
        path="deepspeed_tpu/resilience/runner.py",
        qualname="FaultTolerantRunner.step",
        reason="the fault-tolerant step wrapper: drained-metric reconcile "
               "and chaos/guard bookkeeping ride every training step"),
    HotRoot(
        path="deepspeed_tpu/serving/server.py",
        qualname="InferenceServer._serve_once",
        reason="the serving tick: one thread drives admit/step/fan-out "
               "for every live request — a sync stalls every stream"),
    HotRoot(
        path="deepspeed_tpu/serving/server.py",
        qualname="InferenceServer.health",
        reason="the /healthz payload: polled by the fleet router every "
               "poll tick, so its gauge reads must never touch the device"),
    HotRoot(
        path="deepspeed_tpu/inference/v2/engine_v2.py",
        qualname="InferenceEngineV2.step",
        reason="the v2 engine dispatch: scheduler planning, KV/prefix "
               "bookkeeping and decode fan-in run every engine step"),
    HotRoot(
        path="deepspeed_tpu/serving/fleet.py",
        qualname="FleetRouter.route_generate",
        reason="the per-request routing pick: pure stdlib work over "
               "healthz snapshots — the router host may not even have an "
               "accelerator runtime",
        forbidden=ROUTER_FORBIDDEN),
    HotRoot(
        path="deepspeed_tpu/serving/fleet.py",
        qualname="FleetRouter._poll_once",
        reason="the router poll tick: snapshot/scale-plan every interval",
        forbidden=ROUTER_FORBIDDEN),
    # -- planner/facade roots ----------------------------------------------
    HotRoot(
        path="deepspeed_tpu/comm/compress.py",
        qualname="GradCompressor.build",
        reason="bucket/wire-schedule planning (PR 14): constructed at "
               "engine init but part of the registered comm surface"),
    HotRoot(
        path="deepspeed_tpu/comm/compress.py",
        qualname="GradCompressor.bucket_summaries",
        reason="the overlap-schedule summaries dstpu plan attributes "
               "comm overlap from"),
    # -- callback/surface roots: callers outside the package ---------------
    # (installed listeners, bench harnesses, HTTP dispatch — entry edges a
    # static call graph cannot see; declaring them roots keeps their
    # bodies, and everything they call, inside the taint)
    HotRoot(
        path="deepspeed_tpu/resilience/membership.py",
        qualname="Heartbeat.note_op",
        reason="installed as the comm-op listener: invoked from the "
               "collective facade's _record through listener indirection"),
    HotRoot(
        path="deepspeed_tpu/inference/v2/engine_v2.py",
        qualname="InferenceEngineV2.sched_mark",
        reason="the bench measured-window mark: called between ticks by "
               "bench_serve at the compile boundary"),
    HotRoot(
        path="deepspeed_tpu/runtime/sched.py",
        qualname="DispatchRing.rearm_if_idle",
        reason="public ring surface armed by harnesses between steps"),
    HotRoot(
        path="deepspeed_tpu/runtime/sched.py",
        qualname="DispatchRing.__len__",
        reason="public ring surface: pending-depth probes from benches "
               "and tests ride the hot loop cadence"),
    HotRoot(
        path="deepspeed_tpu/inference/v2/prefix_cache.py",
        qualname="PrefixCache.pinned_blocks",
        reason="cache gauge surface read at tick cadence by harnesses"),
    HotRoot(
        path="deepspeed_tpu/inference/v2/prefix_cache.py",
        qualname="PrefixCache.pinned_block_ids",
        reason="cache pin-set surface consumed by eviction planners and "
               "harnesses at tick cadence"),
)


ESCAPE_HATCHES: Tuple[EscapeHatch, ...] = (
    EscapeHatch(
        path="deepspeed_tpu/runtime/sched.py",
        qualname="DispatchRing.drain",
        mode="sync_ok",
        reason="THE designated readback: one batched device_get over "
               "every pending payload — its bookkeeping callees stay "
               "covered"),
    EscapeHatch(
        path="deepspeed_tpu/inference/v2/engine_v2.py",
        qualname="InferenceEngineV2._collect_oldest",
        mode="sync_ok",
        reason="serving's designated readback, as DispatchRing.drain is "
               "training's: one device_get of a pending step's sampled "
               "tokens (serve/decode_wait). The dispatch half of the step "
               "(plan, build, the step programs) reads nothing back, so a "
               "step can stay in flight while the next is dispatched"),
    EscapeHatch(
        path="deepspeed_tpu/runtime/engine.py",
        qualname="DeepSpeedTPUEngine._drain_metric_ring",
        mode="sync_ok",
        reason="the engine-side drain wrapper: reconciles host copies at "
               "the designated sync point"),
    EscapeHatch(
        path="deepspeed_tpu/runtime/engine.py",
        qualname="DeepSpeedTPUEngine._record_metrics",
        mode="guarded", guard_attr="_async_enabled",
        reason="async fan-in point: the push branch queues device arrays "
               "verbatim and must stay sync-free; the synchronous "
               "fallback branch IS the designed sync path"),
    EscapeHatch(
        path="deepspeed_tpu/runtime/engine.py",
        qualname="DeepSpeedTPUEngine._offload_host_update",
        mode="prune",
        reason="host optimizer step: synchronous by design (streamed "
               "D2H/H2D is the whole point of the offload ladder)"),
    EscapeHatch(
        path="deepspeed_tpu/runtime/engine.py",
        qualname="DeepSpeedTPUEngine._train_batch_param_offload",
        mode="prune",
        reason="the streamed host-offload train step: ditto"),
    EscapeHatch(
        path="deepspeed_tpu/runtime/engine.py",
        qualname="DeepSpeedTPUEngine._host_init_params",
        mode="prune",
        reason="init-time host materialization, not per-step"),
    EscapeHatch(
        path="deepspeed_tpu/runtime/engine.py",
        qualname="DeepSpeedTPUEngine._monitor_step_events",
        mode="sync_ok",
        reason="the single monitor-event formatter: both callers hand it "
               "host copies (the guarded sync record path and the drain "
               "consumer) — its float() normalizes, never blocks"),
    EscapeHatch(
        path="deepspeed_tpu/runtime/engine.py",
        qualname="DeepSpeedTPUEngine._note_oom",
        mode="prune",
        reason="OOM forensics: runs once on a RESOURCE_EXHAUSTED raise, "
               "after the step already died — sync is the point"),
    EscapeHatch(
        path="deepspeed_tpu/resilience/runner.py",
        qualname="FaultTolerantRunner.step",
        mode="guarded", guard_attr="_async_enabled",
        reason="the runner's readback fan-in: the async branch replays "
               "drained host copies; the fallback branch owns ONE "
               "batched device_get and is the designed sync path"),
    EscapeHatch(
        path="deepspeed_tpu/resilience/runner.py",
        qualname="FaultTolerantRunner._maybe_save",
        mode="prune",
        reason="checkpoint save: a deliberate synchronous D2H barrier at "
               "the save boundary (snapshot consistency requires it)"),
    EscapeHatch(
        path="deepspeed_tpu/resilience/runner.py",
        qualname="FaultTolerantRunner._export_monitor_events",
        mode="sync_ok",
        reason="exports already-drained host metric dicts to the monitor "
               "backends — float() normalizes host values"),
    EscapeHatch(
        path="deepspeed_tpu/resilience/guards.py",
        qualname="_finite_report",
        mode="prune",
        reason="non-finite forensics: runs only after the guard trips; "
               "the whole point is to pull the offending values to host"),
    EscapeHatch(
        path="deepspeed_tpu/resilience/membership.py",
        qualname="StragglerDetector.ingest_spans",
        mode="sync_ok",
        reason="consumes host span dicts from the tracer ring snapshot"),
    EscapeHatch(
        path="deepspeed_tpu/runtime/eigenvalue.py",
        qualname="Eigenvalue.compute_eigenvalue",
        mode="prune",
        reason="periodic power-iteration probe on its own schedule "
               "(eigenvalue_every): synchronous convergence loop by "
               "design, never on the steady-state step"),
    EscapeHatch(
        path="deepspeed_tpu/compression/compress.py",
        qualname="Compressor.maybe_freeze_masks",
        mode="prune",
        reason="one-shot sparse-mask freeze at the scheduled boundary "
               "step: a single deliberate readback, then never again"),
    EscapeHatch(
        path="deepspeed_tpu/inference/v2/kv_cache.py",
        qualname="BlockedKVCache.gather_blocks",
        mode="sync_ok",
        reason="THE designated page D2H: the tier planner decided to "
               "demote these blocks; the copy is the operation"),
    EscapeHatch(
        path="deepspeed_tpu/inference/v2/kv_cache.py",
        qualname="BlockedKVCache.scatter_blocks",
        mode="sync_ok",
        reason="THE designated page H2D staging (promotion, prefix "
               "handoff adopt): ditto"),
    EscapeHatch(
        path="deepspeed_tpu/monitor/monitor.py",
        qualname="MonitorMaster.write_events",
        mode="sync_ok",
        reason="normalizes host event values once for every backend; "
               "producers only hand it host copies (drain output)"),
    EscapeHatch(
        path="deepspeed_tpu/serving/metrics.py",
        qualname="ServingMetrics.set_prefix_gauges",
        mode="sync_ok",
        reason="coerces host bookkeeping counters from the prefix-cache "
               "stats dict into gauges"),
    EscapeHatch(
        path="deepspeed_tpu/serving/metrics.py",
        qualname="ServingMetrics.events",
        mode="sync_ok",
        reason="flattens the host counter/gauge snapshot for export"),
    EscapeHatch(
        path="deepspeed_tpu/telemetry/hist.py",
        qualname="LogHistogram.observe",
        mode="sync_ok",
        reason="float() normalizes a host monotonic-stamp difference "
               "into a bucket counter — the SLO histograms are fed "
               "stdlib floats only, never device arrays"),
    EscapeHatch(
        path="deepspeed_tpu/telemetry/hist.py",
        qualname="LogHistogram.bucket_index",
        mode="sync_ok",
        reason="the le-inclusive bucket scan over the same host float "
               "(observe's callee; covered separately because sync_ok "
               "does not exempt callees)"),
    EscapeHatch(
        path="deepspeed_tpu/telemetry/tracer.py",
        qualname="Tracer.tail",
        mode="sync_ok",
        reason="diagnostic slice over the host event ring (the 'last 30s "
               "before quarantine' bundle) — host tuples only"),
    EscapeHatch(
        path="deepspeed_tpu/utils/timer.py",
        qualname="_device_sync",
        mode="sync_ok",
        reason="the timer's opt-in synchronize mode: a deliberate "
               "dispatch-queue flush, off on the hot path by default"),
    EscapeHatch(
        path="deepspeed_tpu/utils/timer.py",
        qualname="Timer.record_external",
        mode="sync_ok",
        reason="records host wall-clock seconds handed in by the caller"),
)


#: the inverse contract: modules that must NEVER run on (or be imported
#: by) a hot path, enforced as lint by DS009 in both directions — an
#: OFFLINE_ONLY module reaching ``jax`` through its module-level import
#: graph is a finding, and a hot-path file importing an OFFLINE_ONLY
#: module is a finding. ``dstpu plan``'s trace replay is offline by
#: contract: it re-reads whole dumps, builds interval sweeps, and does
#: unbounded host work, any of which would wreck a per-step path.
OFFLINE_ONLY_MODULES: Tuple[str, ...] = (
    "deepspeed_tpu/telemetry/attribution.py",
    # the serving-tick replay (`dstpu plan --serve`) — same contract:
    # stdlib-only, file-loadable on jax-less hosts, never on a hot path
    "deepspeed_tpu/telemetry/serve_attribution.py",
    # the cross-rank merge + skew ledger (`dstpu trace merge` / `dstpu
    # plan --cross-rank`) — replays N whole dumps at once; strictly
    # offline, stdlib-only, jax-less-host loadable
    "deepspeed_tpu/telemetry/crossrank.py",
    # the per-request fleet-timeline stitcher (`dstpu reqtrace`): joins
    # router + replica + flight-recorder dumps on the trace id — whole-
    # dump replay, interval sweeps, strictly offline. (telemetry/hist.py
    # is deliberately NOT here: serving/metrics.py feeds its histograms
    # on the serve path, so it lives under DS002 taint instead.)
    "deepspeed_tpu/telemetry/reqtrace.py",
)
