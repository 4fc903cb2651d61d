"""Static regression gate for the hot paths — a thin wrapper over the
dslint DS002 taint rule, so this tripwire and ``bin/dslint`` can never
drift apart: both read the SAME declarations
(``deepspeed_tpu/tools/dslint/hotpath.HOT_ROOTS`` / ``ESCAPE_HATCHES``).

What the declarations enforce (see hotpath.py for the full spec):

  * everything reachable from a registered hot ROOT (the training
    dispatch, the serving tick, the router poll, ...) never regrows
    ``float()``/``.item()``/``device_get``/``block_until_ready`` —
    readback belongs in the declared escape hatches (the drains, the
    guarded fallback branches, the deliberately-synchronous offload
    paths)
  * a registered root or hatch disappearing (renamed without a
    declaration update) is itself a DS002 drift finding

Plus the superset/necessity proof: the taint closure covers every
function the old hand-written per-function registry named (nothing lost
in the v2 migration), and every declared root uniquely covers part of
it (deleting any single root fails here — roots cannot silently rot).
"""

import pathlib

import pytest

from deepspeed_tpu.tools.dslint import lint_paths
from deepspeed_tpu.tools.dslint.hotpath import ESCAPE_HATCHES, HOT_ROOTS
from deepspeed_tpu.tools.dslint.rules.ds002_hot_sync import HotPathSyncRule

pytestmark = pytest.mark.lint

REPO = pathlib.Path(__file__).resolve().parent.parent

# ----------------------------------------------------------------------
# the frozen pre-v2 registry: every function the old per-function
# HOT_PATHS spec table named, as (path, qualname). The taint closure
# from HOT_ROOTS must keep covering ALL of them — this list is a
# snapshot and should only ever GROW (append new entries when a refactor
# moves hot code; never delete to make the proof pass).
# ----------------------------------------------------------------------
LEGACY_COVERAGE = tuple(
    (path, f"{cls}.{fn}" if cls else fn)
    for path, cls, fns in [
        ("deepspeed_tpu/runtime/engine.py", "DeepSpeedTPUEngine",
         ("train_batch", "stack_microbatches", "_shard_batch",
          "_advance_data_schedules", "_ensure_prefetcher",
          "_emit_overlap_spans", "_record_metrics")),
        ("deepspeed_tpu/runtime/sched.py", "DispatchRing",
         ("push", "rearm_if_idle", "store", "take", "requeue", "__len__")),
        ("deepspeed_tpu/runtime/sched.py", "StagedPrefetcher", ("ensure",)),
        ("deepspeed_tpu/runtime/sched.py", "TickLedger",
         ("observe_tick", "reset_window")),
        ("deepspeed_tpu/inference/v2/scheduler.py", None,
         ("snap_bucket", "plan_step")),
        ("deepspeed_tpu/serving/server.py", "InferenceServer",
         ("_serve_once", "_admit_from_queue", "_fan_out", "_reap",
          "_settle_reaped", "_rebalance_kv_tiers", "_observe_ladder",
          "_reconcile_kv", "_active_worstcase", "_active_uids",
          "_note_clean_step", "_trim_prefix_cache", "_prefix_gauges",
          "_cache_evictable_blocks", "_mark", "_emit_tick_spans",
          "_tick_stage_gauges")),
        ("deepspeed_tpu/serving/degradation.py", "DegradationLadder",
         ("observe", "_transition")),
        ("deepspeed_tpu/serving/kv_tier.py", None,
         ("effective_usable_blocks", "plan_demotions",
          "plan_prefix_evictions", "plan_promotions", "tier_pressure")),
        ("deepspeed_tpu/serving/fleet.py", None,
         ("affinity_key", "pick_replica", "plan_scale")),
        ("deepspeed_tpu/serving/fleet.py", "ReplicaHandle",
         ("in_rotation", "snapshot")),
        ("deepspeed_tpu/inference/v2/prefix_cache.py", "PrefixCache",
         ("lookup", "admit_match", "_pin", "_keys", "insert_from_seq",
          "release_seq", "plan_evictions", "evict_blocks",
          "evictable_blocks", "over_cap_blocks", "cached_blocks",
          "pinned_blocks", "pinned_block_ids", "owns", "snapshot")),
        ("deepspeed_tpu/inference/v2/kv_offload.py", None,
         ("quantize_pages", "dequantize_pages", "_page_absmax")),
        ("deepspeed_tpu/runtime/dataloader.py", "PrefetchLoader",
         ("_worker", "__next__")),
        ("deepspeed_tpu/telemetry/tracer.py", "Tracer",
         ("span", "instant", "complete", "counter", "_emit")),
        ("deepspeed_tpu/telemetry/tracer.py", "_Span",
         ("__enter__", "__exit__")),
        ("deepspeed_tpu/comm/compress.py", None,
         ("quantize_wire", "dequantize_wire", "ef_step",
          "reduce_scatter_impl", "all_reduce_impl", "_exchange",
          "_regather", "axis_world", "plan_buckets")),
        ("deepspeed_tpu/comm/compress.py", "GradCompressor",
         ("make_sync_fn", "bucket_summaries")),
        ("deepspeed_tpu/comm/guard.py", None,
         ("note_comm_op", "next_op_seq")),
        ("deepspeed_tpu/resilience/membership.py", "Heartbeat",
         ("note_op",)),
        ("deepspeed_tpu/telemetry/memory.py", "MemorySampler",
         ("on_drain", "sample", "_collect")),
        ("deepspeed_tpu/telemetry/compiles.py", "CompileWatched",
         ("__call__",)),
    ]
    for fn in fns
)


def _resolved_roots(graph, roots=HOT_ROOTS):
    keys = {}
    for root in roots:
        k = graph.resolve(root.path, root.qualname)
        assert k is not None, (
            f"hot root {root.qualname} no longer resolves in {root.path} "
            f"— update hotpath.py HOT_ROOTS alongside the refactor")
        keys[k] = root
    return keys

def _prune_keys(graph):
    out = set()
    for h in ESCAPE_HATCHES:
        if h.mode != "prune":
            continue
        k = graph.resolve(h.path, h.qualname)
        if k is not None:
            out.add(k)
    return out


def test_declared_roots_still_cover_the_load_bearing_surfaces():
    """The declaration content IS the contract: shrinking it is loud."""
    by_qn = {r.qualname: r for r in HOT_ROOTS}
    for qn in ("DeepSpeedTPUEngine.train_batch", "FaultTolerantRunner.step",
               "InferenceServer._serve_once", "InferenceEngineV2.step",
               "FleetRouter.route_generate", "FleetRouter._poll_once"):
        assert qn in by_qn, f"hot root {qn} was dropped from HOT_ROOTS"
    hatches = {(h.qualname, h.mode) for h in ESCAPE_HATCHES}
    assert ("DispatchRing.drain", "sync_ok") in hatches
    assert ("DeepSpeedTPUEngine._drain_metric_ring", "sync_ok") in hatches
    guarded = {h.qualname: h.guard_attr for h in ESCAPE_HATCHES
               if h.mode == "guarded"}
    assert guarded.get("DeepSpeedTPUEngine._record_metrics") == \
        "_async_enabled"


def test_hot_paths_have_no_host_sync():
    """Lint the whole package with DS002 only (the taint needs every
    file to chase call edges); any finding — including root/hatch drift
    from a rename — fails."""
    result = lint_paths([str(REPO / "deepspeed_tpu")], root=str(REPO),
                        rules=[HotPathSyncRule()])
    assert not result.findings, (
        "hot path gained host synchronization (or a declaration "
        "drifted):\n  "
        + "\n  ".join(f.render() for f in result.findings)
        + "\nroute readback through a declared escape hatch, or update "
          "deepspeed_tpu/tools/dslint/hotpath.py alongside a deliberate "
          "refactor")


def test_taint_closure_is_a_superset_of_the_legacy_registry(
        package_callgraph):
    """Nothing the old per-function registry covered fell out of the
    taint closure: every frozen legacy entry is reachable from the
    declared roots (minus the declared prune hatches)."""
    g = package_callgraph
    reached = g.reachable_from(sorted(_resolved_roots(g)),
                               prune=_prune_keys(g))
    missing = []
    for path, qn in LEGACY_COVERAGE:
        k = g.resolve(path, qn)
        assert k is not None, (
            f"legacy-coverage entry {path}::{qn} no longer exists — "
            f"append its successor to LEGACY_COVERAGE (do not delete)")
        if k not in reached:
            missing.append(k)
    assert not missing, (
        "taint closure LOST legacy hot-path coverage (a call edge or "
        "root declaration broke):\n  " + "\n  ".join(missing))


def test_every_root_is_necessary(package_callgraph):
    """Deleting any single HOT_ROOTS entry loses coverage: each root
    uniquely covers at least one function (a legacy entry or itself).
    A root that covers nothing uniquely is dead weight that would let
    its surface silently drop out of the taint."""
    g = package_callgraph
    roots = _resolved_roots(g)
    prune = _prune_keys(g)
    full = g.reachable_from(sorted(roots), prune=prune)
    legacy_keys = {g.resolve(p, q) for p, q in LEGACY_COVERAGE}
    for key, root in sorted(roots.items()):
        rest = [k for k in roots if k != key]
        without = g.reachable_from(sorted(rest), prune=prune)
        unique = (set(full) - set(without)) & (legacy_keys | {key})
        assert unique, (
            f"root {root.qualname} covers nothing uniquely — removing "
            f"it from HOT_ROOTS changes no coverage, so either a new "
            f"root subsumed it (delete the stale one deliberately and "
            f"update this proof) or the declaration drifted")


# ----------------------------------------------------------------------
# the served step: dispatch reads nothing back, collection is THE readback
# ----------------------------------------------------------------------
ENGINE_V2 = "deepspeed_tpu/inference/v2/engine_v2.py"
COLLECT = "InferenceEngineV2._collect_oldest"


def test_the_served_steps_readback_is_one_declared_function():
    """``engine_v2.py`` is no longer exempt file-wide: numpy copies and
    device syncs are forbidden in everything ``step`` reaches, except in the
    one function that collects a pending step (as ``DispatchRing.drain`` is
    for training)."""
    from deepspeed_tpu.tools.dslint.hotpath import HOST_NUMPY_FILES
    assert ENGINE_V2 not in HOST_NUMPY_FILES
    hatches = {(h.path, h.qualname): h.mode for h in ESCAPE_HATCHES}
    assert hatches.get((ENGINE_V2, COLLECT)) == "sync_ok"
    assert not any(path == ENGINE_V2 and qn != COLLECT
                   for path, qn in hatches)


@pytest.mark.parametrize("half", ["dispatch", "collect"])
def test_the_steps_dispatch_half_reads_nothing_back(half):
    """With the hatch taken away, every finding under ``step`` lies in the
    collecting function: ``step`` itself, ``_dispatch`` and whatever they
    call (planning, block bookkeeping, building the batch, the step
    programs' dispatch) hold no sync, so a step can stay in flight while
    the next one is dispatched."""
    roots = tuple(r for r in HOT_ROOTS
                  if r.qualname == "InferenceEngineV2.step")
    assert roots
    result = lint_paths(
        [str(REPO / "deepspeed_tpu")], root=str(REPO),
        rules=[HotPathSyncRule(roots=roots, hatches=())])
    found = [f for f in result.findings if f.path == ENGINE_V2]
    where = {f.anchor.split(":")[0] for f in found}
    if half == "dispatch":
        assert where <= {COLLECT}, (
            "the dispatch half of engine.step gained a host sync:\n  "
            + "\n  ".join(f.render() for f in found))
    else:
        assert where == {COLLECT}, "the step's one wait has moved: " \
            "declare where the pending step is read in hotpath.py"
