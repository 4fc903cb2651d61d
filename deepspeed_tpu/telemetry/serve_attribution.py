"""``dstpu plan --serve`` — serving-tick attribution and siege-knob planning.

The serving analog of ``attribution.py`` (the DeepCompile loop of PR 7,
arxiv 2504.09983, applied to the serve tick): replay a bench_serve /
``DSTPU_TRACE`` dump and explain where every serving tick went, then turn
the dominant pressure signal into ONE executable serving-config override
with a machine-checkable counter prediction the bench can re-run and judge
(the ZeRO-Offload-style host-tier economics of arxiv 2101.06840, tuned per
traffic mix instead of per engineer):

1. **Tick attribution** — every ``serve/tick`` window (the retro-span the
   serve loop emits around each working tick; older dumps fall back to the
   raw ``serve/engine_step`` spans) is decomposed into *exclusive* stages
   on the serve-loop track — admission, prefill, decode, demote, promote,
   drain, residual — by the same priority interval sweep as the training
   planner, so the per-tick ledger provably sums to the window
   (``residual`` is the exact remainder; over-attribution surfaces as
   ``tie_out_error``, bounded by the clock-skew tolerance).
2. **Joins** — the per-request retro-spans (``serve/queued`` /
   ``serve/prefill`` / ``serve/decode``) roll up to p50/p99 TTFT/TPOT per
   degradation-ladder level; the ``serve/*`` + ``mem/*`` counter tracks
   (KV bytes, prefix cache, tier state) report last/max/p95/p99 per
   series; the instant families (``serve/ladder``, ``serve/kv_demote``,
   ``serve/kv_recalibrate``, ``serve/prefix_evict``, backpressure kinds)
   are counted so a whole siege episode reads from one report.
3. **Regression ledger** — ``serve_plan_baseline.json`` (dslint/plan
   ratchet idiom): per-stage per-tick quantiles, workload-scoped by trace
   basename; regression -> exit 1, improvements surface as stale entries
   expired only via ``--write-baseline``.
4. **Proposals** — a rule table maps the dominant pressure signal to ONE
   serving-config override (raise ``kv_demote_watermark`` when demote
   churn starves decode; raise ``host_kv_budget_bytes`` when sheds happen
   with idle host budget; raise ``prefix_cache_max_blocks`` when the hit
   ratio is low under eviction pressure; widen ``ladder_hysteresis`` when
   brownout flaps) carrying a deterministic counter prediction
   (``{counter, op, value}``) that ``autotuning.serve_verify`` re-executes
   against the same seeded bench_serve preset and judges EXACTLY,
   persisting verdicts under ``plan.serve_verifications`` in
   ``autotuning_results.json``.

Offline-only, by contract: stdlib-only at module level and file-loadable
standalone (``bin/dstpu plan --serve`` works on jax-less hosts), listed in
``tools/dslint/hotpath.py`` ``OFFLINE_ONLY_MODULES`` — no registered hot
path may import it, and it never imports jax.
"""

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple


def _load_trace_names():
    """File-load ``telemetry/names.py`` from the sibling path — never a
    package import: this module loads standalone on jax-less hosts. The
    stage table's NAMES live in the registry, so renaming a serve emitter
    is a DS007 finding instead of silently reattributing to residual."""
    import importlib.util
    mod = sys.modules.get("dstpu_trace_names")
    if mod is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "names.py")
        spec = importlib.util.spec_from_file_location(
            "dstpu_trace_names", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules["dstpu_trace_names"] = mod
    return mod


_NAMES = _load_trace_names()

EXIT_OK = 0
EXIT_REGRESSION = 1
EXIT_UNREADABLE = 2

SERVE_PLAN_VERSION = 1
SERVE_PLAN_BASELINE_VERSION = 1
SERVE_PLAN_BASELINE_NAME = "serve_plan_baseline.json"
SERVE_PLAN_ARTIFACT_ENV = "DSTPU_SERVE_PLAN_ARTIFACT"
DEFAULT_SERVE_PLAN_ARTIFACT = "serve_plan.json"

#: stage keys, in ledger/report order. ``residual`` is always last: the
#: remainder of the tick the sweep could not attribute (ladder/reconcile/
#: gauge bookkeeping, engine host work outside the prefill/decode kernels).
STAGES = ("admission", "prefill", "decode", "demote", "promote", "drain",
          "residual")

#: exclusive-sweep priority — at any instant the HIGHEST-priority covering
#: span owns the time. The page movers (demote/promote) outrank the step
#: phases, the step phases outrank request settling, and admission is the
#: outermost attributable catch-all. ``serve/engine_step`` is NOT a stage:
#: its prefill/decode interior attributes, the rest is residual.
_PRIORITY = {"demote": 6, "promote": 5, "prefill": 4, "decode": 3,
             "drain": 2, "admission": 1}

#: per-window tie-out tolerance, same contract as attribution.py: stage
#: sums may exceed the tick window by at most this fraction (sub-ms clock
#: skew between the retro tick window and the stage spans inside it).
TIE_OUT_TOLERANCE = 0.05

#: share of tick time the prefill stage must hold before the
#: ``prefill_chunk_tokens`` rule calls prefill dominant. A share of host
#: time: it moves with the host's load, unlike the counters the rule's
#: prediction is judged by.
PREFILL_DOMINANT_SHARE = 0.35

_TICK_NAME = _NAMES.SERVE_TICK_NAME

#: span name -> exclusive stage key: the names come from the
#: registry (one declaration, DS007-enforced); the sweep
#: priorities stay here next to the sweep
_STAGE_OF = dict(_NAMES.SERVE_STAGE_OF)

#: ServingConfig defaults the proposal rules fall back to when the input
#: is a bare trace with no bench_serve provenance (a literal, NOT an
#: import: this module loads standalone by contract; tests pin the copies
#: against serving.server.ServingConfig)
SERVING_DEFAULTS = {
    "max_queue_depth": 256,
    "kv_high_watermark": 0.95,
    "kv_offload_enabled": False,
    "host_kv_budget_bytes": 256 << 20,
    "kv_demote_watermark": 0.90,
    "kv_demote_watermark_brownout": 0.60,
    "prefix_cache_enabled": False,
    "prefix_cache_max_blocks": 0,
    "brownout_pressure": 0.85,
    "shed_pressure": 0.97,
    "ladder_hysteresis": 0.10,
    "ladder_cooldown_ticks": 20,
    "scheduler": {"prefill_chunk_tokens": 0},
}


class PlanError(Exception):
    """Unreadable/empty input — maps to CLI exit code 2."""


# ---------------------------------------------------------------------------
# event loading / normalization (standalone copies — see module docstring)
# ---------------------------------------------------------------------------
class Ev:
    """One normalized trace event (Chrome-trace microsecond clock)."""
    __slots__ = ("name", "cat", "ph", "ts", "dur", "tid", "args")

    def __init__(self, name, cat, ph, ts, dur, tid, args):
        self.name = name
        self.cat = cat
        self.ph = ph
        self.ts = float(ts)
        self.dur = float(dur)
        self.tid = tid
        self.args = args or {}

    @property
    def end(self) -> float:
        return self.ts + self.dur


def events_from_chrome(obj: Any) -> List[Ev]:
    """Normalize a Chrome-trace object (dict with ``traceEvents`` or a bare
    event list) into ``Ev`` records; metadata ("M") events are dropped."""
    if isinstance(obj, dict):
        raw = obj.get("traceEvents")
        if raw is None:
            raise PlanError("not a Chrome trace: no 'traceEvents' key")
    elif isinstance(obj, list):
        raw = obj
    else:
        raise PlanError(f"not a Chrome trace: top-level {type(obj).__name__}")
    out = []
    for e in raw:
        if not isinstance(e, dict) or e.get("ph") == "M":
            continue
        try:
            out.append(Ev(e.get("name", "?"), e.get("cat", ""), e.get("ph"),
                          float(e.get("ts", 0.0)), float(e.get("dur", 0.0)),
                          e.get("tid"), e.get("args")))
        except (TypeError, ValueError):
            continue   # malformed row: skip, never die mid-replay
    return out


def quantile(sorted_vals: List[float], q: float) -> float:
    """Exact sample quantile, the repo-wide rule (``tracer._quantile`` /
    ``attribution.quantile``): value at index ``min(int(q*n), n-1)``.
    Deliberately a local copy, NOT an import — standalone-load contract;
    tests/test_serve_plan.py pins the copies equal."""
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(int(q * len(sorted_vals)), len(sorted_vals) - 1)]


def load_input(path: str) -> Tuple[List[Ev], Dict[str, Any]]:
    """Load a serve-plan input: either a raw dstrace Chrome dump, or a
    bench_serve report JSON whose ``provenance.trace_path`` locates the
    dump (relative paths resolve against the report's directory). Returns
    ``(events, meta)`` where meta carries trace_path / provenance /
    bench_counters / prefix for the joins and the proposal rules."""
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as e:
        raise PlanError(f"cannot read {path}: {e}") from e
    meta: Dict[str, Any] = {"input": path, "trace_path": path,
                            "provenance": None, "bench_counters": None,
                            "prefix": None}
    if isinstance(obj, dict) and "traceEvents" in obj:
        return events_from_chrome(obj), meta
    if isinstance(obj, dict) and ("provenance" in obj or "counters" in obj):
        prov = obj.get("provenance") or {}
        trace_path = prov.get("trace_path")
        if not trace_path:
            raise PlanError(
                f"bench_serve report {path} has no provenance.trace_path — "
                "re-run bench_serve with --trace (or DSTPU_TRACE) so the "
                "plan can locate the dump")
        if not os.path.isabs(trace_path):
            trace_path = os.path.join(os.path.dirname(os.path.abspath(path)),
                                      trace_path)
        if not os.path.exists(trace_path):
            raise PlanError(f"trace {trace_path} (from {path} provenance) "
                            "does not exist")
        try:
            with open(trace_path) as f:
                trace_obj = json.load(f)
        except (OSError, ValueError) as e:
            raise PlanError(f"cannot read trace {trace_path}: {e}") from e
        meta.update(trace_path=trace_path, provenance=prov,
                    bench_counters=obj.get("counters"),
                    prefix=obj.get("prefix"))
        return events_from_chrome(trace_obj), meta
    raise PlanError(f"{path} is neither a Chrome trace nor a bench_serve "
                    "report (no traceEvents / provenance)")


# ---------------------------------------------------------------------------
# tick windows + exclusive sweep
# ---------------------------------------------------------------------------
def tick_windows(events: List[Ev]) -> Tuple[List[Dict[str, Any]], str]:
    """The tick windows to attribute. ``serve/tick`` retro-spans (one per
    working serve tick) are the primary anchor; dumps from before the tick
    span existed fall back to the raw ``serve/engine_step`` spans (the
    ledger then misses admission/drain work outside the step — noted via
    the returned mode)."""
    ticks = sorted((e for e in events
                    if e.ph == "X" and e.name == _TICK_NAME),
                   key=lambda e: e.ts)
    if ticks:
        return [{"start_us": e.ts, "end_us": e.end,
                 "tick": e.args.get("tick")} for e in ticks], "tick"
    steps = sorted((e for e in events
                    if e.ph == "X" and e.name == "serve/engine_step"),
                   key=lambda e: e.ts)
    if not steps:
        raise PlanError("no serving tick spans in trace (serve/tick and "
                        "serve/engine_step both absent) — was the server "
                        "run traced with DSTPU_TRACE?")
    return [{"start_us": e.ts, "end_us": e.end, "tick": None}
            for e in steps], "engine_step"


def main_track(events: List[Ev]) -> Optional[Any]:
    """The tid that emits the tick spans — the serve loop's track."""
    counts: Dict[Any, int] = {}
    for e in events:
        if e.ph == "X" and e.name in (_TICK_NAME, "serve/engine_step"):
            counts[e.tid] = counts.get(e.tid, 0) + 1
    if not counts:
        return None
    return max(sorted(counts, key=str), key=counts.get)


def _exclusive_sweep(intervals: List[Tuple[float, float, str]],
                     w0: float, w1: float) -> Dict[str, float]:
    """Exclusive per-stage time over [w0, w1]: at every instant the
    highest-priority covering interval owns it. Intervals are pre-clipped."""
    out = {s: 0.0 for s in STAGES if s != "residual"}
    if not intervals:
        return out
    pts = sorted({w0, w1, *(i[0] for i in intervals),
                  *(i[1] for i in intervals)})
    for a, b in zip(pts, pts[1:]):
        if b <= a:
            continue
        mid = (a + b) / 2.0
        best = None
        for s, e, stage in intervals:
            if s <= mid < e and (best is None
                                 or _PRIORITY[stage] > _PRIORITY[best]):
                best = stage
        if best is not None:
            out[best] += b - a
    return out


def _union(intervals: List[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


# ---------------------------------------------------------------------------
# joins: request latency / counter tracks / instant families
# ---------------------------------------------------------------------------
def request_latency(events: List[Ev]) -> Dict[str, Any]:
    """p50/p99 TTFT/TPOT per degradation-ladder level, rebuilt from the
    per-request retro-spans exactly as bench_serve does (TTFT = queued.dur
    + prefill.dur; TPOT = decode.dur / (tokens - 1)); the ``level`` arg is
    the ladder level the request was admitted under."""
    queued: Dict[Any, Tuple[float, str]] = {}
    prefill: Dict[Any, float] = {}
    decode: Dict[Any, Tuple[float, int]] = {}
    for e in events:
        if e.ph != "X" or "uid" not in e.args:
            continue
        uid = e.args["uid"]
        if e.name == "serve/queued":
            queued[uid] = (e.dur, str(e.args.get("level", "unknown")))
        elif e.name == "serve/prefill":
            prefill[uid] = e.dur
        elif e.name == "serve/decode":
            decode[uid] = (e.dur, int(e.args.get("tokens", 0) or 0))
    per_level: Dict[str, Dict[str, List[float]]] = {}
    for uid, dur in prefill.items():
        if uid not in queued:
            continue
        qdur, level = queued[uid]
        bucket = per_level.setdefault(level, {"ttft_us": [], "tpot_us": []})
        bucket["ttft_us"].append(qdur + dur)
        if uid in decode:
            ddur, tokens = decode[uid]
            if tokens > 1:
                bucket["tpot_us"].append(ddur / (tokens - 1))
    out: Dict[str, Any] = {"levels": {}, "requests": len(prefill)}
    all_ttft: List[float] = []
    all_tpot: List[float] = []
    for level in sorted(per_level):
        b = per_level[level]
        row: Dict[str, Any] = {"count": len(b["ttft_us"])}
        for key, vals in (("ttft", b["ttft_us"]), ("tpot", b["tpot_us"])):
            vals.sort()
            row[f"{key}_p50_ms"] = round(quantile(vals, 0.5) / 1e3, 4)
            row[f"{key}_p99_ms"] = round(quantile(vals, 0.99) / 1e3, 4)
        out["levels"][level] = row
        all_ttft.extend(b["ttft_us"])
        all_tpot.extend(b["tpot_us"])
    all_ttft.sort()
    all_tpot.sort()
    out["ttft_p50_ms"] = round(quantile(all_ttft, 0.5) / 1e3, 4)
    out["ttft_p99_ms"] = round(quantile(all_ttft, 0.99) / 1e3, 4)
    out["tpot_p50_ms"] = round(quantile(all_tpot, 0.5) / 1e3, 4)
    out["tpot_p99_ms"] = round(quantile(all_tpot, 0.99) / 1e3, 4)
    return out


def counter_tracks(events: List[Ev]) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """The ``serve/*`` + ``mem/*`` counter tracks rolled up per series:
    last/max/p95/p99/count — the read side of the KV-bytes, prefix-cache,
    tier-state and dsmem HBM tracks (same stats ``Tracer.counter_series``
    now reports live)."""
    series: Dict[str, Dict[str, List[float]]] = {}
    for e in events:
        if e.ph != "C" or not e.args:
            continue
        if not (e.name.startswith("serve/") or e.name.startswith("mem/")):
            continue
        bucket = series.setdefault(e.name, {})
        for key, val in e.args.items():
            try:
                v = float(val)
            except (TypeError, ValueError):
                continue
            bucket.setdefault(key, []).append(v)
    out: Dict[str, Dict[str, Dict[str, Any]]] = {}
    for name in sorted(series):
        out[name] = {}
        for key in sorted(series[name]):
            vals = series[name][key]
            last = vals[-1]
            vals = sorted(vals)
            out[name][key] = {"last": last, "max": vals[-1],
                              "p95": quantile(vals, 0.95),
                              "p99": quantile(vals, 0.99),
                              "count": len(vals)}
    return out


def instant_families(events: List[Ev]) -> Dict[str, Any]:
    """Counts of the serve instant families plus the structured details a
    siege episode reconstructs from: ladder edges keyed ``frm->to``,
    backpressure by kind, demotion/promotion/recalibration/eviction
    volume."""
    counts: Dict[str, int] = {}
    ladder: Dict[str, int] = {}
    backpressure: Dict[str, int] = {}
    demoted_bytes = promoted_bytes = evicted_blocks = 0
    for e in events:
        if e.ph != "i" or not e.name.startswith("serve/"):
            continue
        counts[e.name] = counts.get(e.name, 0) + 1
        if e.name == "serve/ladder":
            key = f"{e.args.get('frm')}->{e.args.get('to')}"
            ladder[key] = ladder.get(key, 0) + 1
        elif e.name == "serve/backpressure":
            kind = str(e.args.get("kind", "?"))
            backpressure[kind] = backpressure.get(kind, 0) + 1
        elif e.name == "serve/kv_demote":
            demoted_bytes += int(e.args.get("bytes", 0) or 0)
        elif e.name == "serve/kv_promote":
            promoted_bytes += int(e.args.get("bytes", 0) or 0)
        elif e.name == "serve/prefix_evict":
            evicted_blocks += int(e.args.get("blocks", 0) or 0)
    return {"counts": dict(sorted(counts.items())),
            "ladder_edges": dict(sorted(ladder.items())),
            "backpressure": dict(sorted(backpressure.items())),
            "demoted_bytes": demoted_bytes,
            "promoted_bytes": promoted_bytes,
            "prefix_evicted_blocks": evicted_blocks}


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------
def attribute_serve(events: List[Ev], source: str = "<events>",
                    meta: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Replay a serving trace into the serve-plan report: per-tick
    exclusive stage ledger (ties out to each tick window within
    ``TIE_OUT_TOLERANCE``), aggregate per-tick quantiles, the request/
    counter/instant joins, observed config, and proposals."""
    meta = meta or {}
    windows, window_mode = tick_windows(events)
    track = main_track(events)
    spans = [e for e in events if e.ph == "X"]
    ledger = []
    for i, w in enumerate(windows):
        w0, w1 = w["start_us"], w["end_us"]
        on_track, off_track = [], []
        for e in spans:
            st = _STAGE_OF.get(e.name)
            if st is None or e.end <= w0 or e.ts >= w1:
                continue
            clipped = (max(e.ts, w0), min(e.end, w1))
            if track is None or e.tid == track:
                on_track.append((clipped[0], clipped[1], st))
            else:
                off_track.append((clipped[0], clipped[1], st))
        excl = _exclusive_sweep(on_track, w0, w1)
        dur = w1 - w0
        attributed = sum(excl.values())
        residual = dur - attributed
        overlapped: Dict[str, float] = {}
        for st in set(s for _, _, s in off_track):
            overlapped[st] = _union([(a, b) for a, b, s in off_track
                                     if s == st])
        stages_us = {s: excl.get(s, 0.0) for s in STAGES if s != "residual"}
        stages_us["residual"] = max(residual, 0.0)
        ledger.append({
            "index": i,
            "tick": w["tick"],
            "start_us": round(w0, 3),
            "dur_us": round(dur, 3),
            "stages_us": {k: round(v, 3) for k, v in stages_us.items()},
            "overlapped_us": {k: round(v, 3)
                              for k, v in sorted(overlapped.items())},
            # tie-out proof: attributed time never exceeds the window
            # beyond clock skew; residual is the exact remainder
            "tie_out_error": round(max(attributed - dur, 0.0)
                                   / dur if dur > 0 else 0.0, 6),
        })
    total_us = sum(w["dur_us"] for w in ledger) or 1.0
    aggregate: Dict[str, Dict[str, float]] = {}
    for s in STAGES:
        per_tick_ms = sorted(w["stages_us"][s] / 1e3 for w in ledger)
        total_stage = sum(w["stages_us"][s] for w in ledger)
        aggregate[s] = {
            "total_ms": round(total_stage / 1e3, 3),
            "share": round(total_stage / total_us, 4),
            "mean_tick_ms": round(sum(per_tick_ms) / len(per_tick_ms), 4),
            "p50_tick_ms": round(quantile(per_tick_ms, 0.5), 4),
            "p95_tick_ms": round(quantile(per_tick_ms, 0.95), 4),
            "p99_tick_ms": round(quantile(per_tick_ms, 0.99), 4),
        }
    cfg = dict(SERVING_DEFAULTS)
    prov = meta.get("provenance") or {}
    for key, val in (prov.get("serving_config") or {}).items():
        cfg[key] = val
    report = {
        "version": SERVE_PLAN_VERSION,
        "source": source,
        "trace": meta.get("trace_path", source),
        "window_mode": window_mode,
        "windows": ledger,
        "ticks_total": len(ledger),
        "window_ms_total": round(total_us / 1e3, 3),
        "tick_ms_p50": round(quantile(
            sorted(w["dur_us"] / 1e3 for w in ledger), 0.5), 4),
        "aggregate": aggregate,
        "requests": request_latency(events),
        "counters": counter_tracks(events),
        "instants": instant_families(events),
        "config_observed": cfg,
        "provenance": prov or None,
        "bench_counters": meta.get("bench_counters"),
        "prefix": meta.get("prefix"),
    }
    report["proposals"] = propose_serve(report)
    return report


# ---------------------------------------------------------------------------
# proposals: dominant pressure signal -> ONE serving-config override
# ---------------------------------------------------------------------------
def _signals(report: Dict[str, Any]) -> Dict[str, Any]:
    """The deterministic counter signals the rule table fires on —
    bench_serve's counter proof set when the input was a report, else the
    equivalents rebuilt from the trace's instants/counter tracks."""
    bench = report.get("bench_counters") or {}
    inst = report.get("instants", {})
    tracks = report.get("counters", {})
    cfg = report.get("config_observed", {})
    sheds = bench.get("sheds")
    if sheds is None:
        sheds = inst.get("backpressure", {}).get("shed", 0)
    brownouts = bench.get("brownout_entries")
    if brownouts is None:
        brownouts = sum(n for key, n in inst.get("ladder_edges", {}).items()
                        if key.endswith("->brownout"))
    demoted_bytes = bench.get("demoted_bytes")
    if demoted_bytes is None:
        demoted_bytes = inst.get("demoted_bytes", 0)
    demotions = bench.get("demotions")
    if demotions is None:
        demotions = inst.get("counts", {}).get("serve/kv_demote", 0)
    evictions = bench.get("prefix_evictions")
    if evictions is None:
        evictions = inst.get("prefix_evicted_blocks", 0)
    prefix = report.get("prefix") or {}
    hit_ratio = prefix.get("prefix_hit_ratio")
    host_frac_max = None
    budget = cfg.get("host_kv_budget_bytes") or 0
    host_track = tracks.get("serve/kv_tier", {}).get("host_bytes")
    if host_track is not None and budget > 0:
        host_frac_max = round(host_track["max"] / budget, 4)
    # scheduler proof set (report["scheduler"], mirrored into the bench
    # counters): the worst tick's prefill tokens — the exact quantity the
    # chunk cap bounds by construction
    sched = report.get("scheduler") or {}
    max_prefill = bench.get("max_prefill_tokens_per_tick")
    if max_prefill is None:
        max_prefill = sched.get("max_prefill_tokens_per_tick")
    return {"sheds": int(sheds or 0),
            "brownout_entries": int(brownouts or 0),
            "demotions": int(demotions or 0),
            "demoted_bytes": int(demoted_bytes or 0),
            "prefix_evictions": int(evictions or 0),
            "prefix_hit_ratio": hit_ratio,
            "host_frac_max": host_frac_max,
            "max_prefill_tokens_per_tick": int(max_prefill or 0)}


def propose_serve(report: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The serving rule table: each entry maps a dominant pressure signal
    to ONE executable serving-config override plus an exact counter
    prediction (``{counter, op, value}`` judged against the re-run's
    bench_serve counters by ``autotuning.serve_verify``). Deterministic:
    ordered by score, ties by rule id."""
    agg = report["aggregate"]
    cfg = report["config_observed"]
    sig = _signals(report)
    props: List[Dict[str, Any]] = []

    churn_share = round(agg["demote"]["share"] + agg["promote"]["share"], 4)
    cur_wm = float(cfg.get("kv_demote_watermark", 0.90))
    if sig["demotions"] > 0 and churn_share >= 0.05 and cur_wm < 0.95:
        # decode starved by demote churn: the tick spends more time moving
        # pages than the load justifies — demote later. The step is
        # deliberately LARGE (+0.25): demotion volume responds to the line
        # with real but bounded run-to-run jitter, and a verifiable
        # prediction needs effect size well above that noise (a +0.05
        # nudge would flip verdicts on scheduler timing, not on the knob).
        new_wm = round(min(cur_wm + 0.25, 0.95), 2)
        props.append({
            "id": "raise_kv_demote_watermark",
            "signal": "demote_churn",
            "score": churn_share,
            "knob": "kv_demote_watermark",
            "overrides": {"serving": {"kv_demote_watermark": new_wm}},
            "reason": f"demote+promote churn is {churn_share:.0%} of tick "
                      f"time ({sig['demotions']} demotions, "
                      f"{sig['demoted_bytes']} bytes) at watermark "
                      f"{cur_wm}: the tier thrashes pages instead of "
                      "decoding — demote later",
            "predicted": {"counter": "demoted_bytes", "op": "<=",
                          "value": sig["demoted_bytes"],
                          "baseline": sig["demoted_bytes"],
                          "unit": "bytes"},
        })
    host_frac = sig["host_frac_max"]
    if cfg.get("kv_offload_enabled") and sig["sheds"] > 0 \
            and host_frac is not None and host_frac < 0.5:
        # shedding while the host tier sits half-idle: the overflow valve
        # exists but is sized too small to absorb this traffic mix
        cur_budget = int(cfg.get("host_kv_budget_bytes", 256 << 20))
        props.append({
            "id": "raise_host_kv_budget_bytes",
            "signal": "sheds_with_idle_host_budget",
            "score": round(min(sig["sheds"], 20) / 20.0, 4),
            "knob": "host_kv_budget_bytes",
            "overrides": {"serving": {"host_kv_budget_bytes":
                                      cur_budget * 2}},
            "reason": f"{sig['sheds']} sheds while the host KV tier peaked "
                      f"at {host_frac:.0%} of its budget: overload is "
                      "degrading to 429 with headroom left — double the "
                      "host budget so pressure degrades to slower first",
            "predicted": {"counter": "sheds", "op": "<=",
                          "value": max(sig["sheds"] - 1, 0),
                          "baseline": sig["sheds"],
                          "unit": "requests"},
        })
    cur_cap = int(cfg.get("prefix_cache_max_blocks", 0) or 0)
    hit = sig["prefix_hit_ratio"]
    if cfg.get("prefix_cache_enabled") and cur_cap > 0 \
            and sig["prefix_evictions"] > 0 and (hit is None or hit < 0.6):
        # the soft cap trims reusable pages the traffic mix would have hit:
        # a bigger cap can only evict fewer blocks under the same seeded
        # load (the exact prediction); the hit ratio rises with it
        hit_txt = "unknown" if hit is None else f"{hit:.0%}"
        props.append({
            "id": "raise_prefix_cache_max_blocks",
            "signal": "low_hit_ratio_with_eviction_pressure",
            "score": round(1.0 - (hit if hit is not None else 0.5), 4),
            "knob": "prefix_cache_max_blocks",
            "overrides": {"serving": {"prefix_cache_max_blocks":
                                      cur_cap * 2}},
            "reason": f"prefix-cache hit ratio {hit_txt} with "
                      f"{sig['prefix_evictions']} blocks evicted at cap "
                      f"{cur_cap}: the cap trims pages the mix would have "
                      "reused — double it",
            "predicted": {"counter": "prefix_evictions", "op": "<=",
                          "value": sig["prefix_evictions"],
                          "baseline": sig["prefix_evictions"],
                          "unit": "blocks",
                          "hit_ratio_baseline": hit},
        })
    sched_cfg = dict(cfg.get("scheduler") or {})
    cur_chunk = int(sched_cfg.get("prefill_chunk_tokens", 0) or 0)
    maxp = sig["max_prefill_tokens_per_tick"]
    prefill_share = agg["prefill"]["share"]
    if maxp > 0 and prefill_share >= PREFILL_DOMINANT_SHARE \
            and agg["decode"]["share"] > 0 \
            and (cur_chunk == 0 or maxp > cur_chunk // 2):
        # decode-first starvation: prefill dominates the tick while decodes
        # wait behind it (the p99 prefill tick IS the TPOT spike a long
        # prompt causes) — cap chunked prefill at half the observed worst
        # tick. KV-block-aligned (16-token pages in the bench geometry) so
        # capped boundaries stay on page granularity; the planner then
        # bounds every tick's prefill tokens by the cap BY CONSTRUCTION,
        # which is exactly the predicted counter bound the re-run judges.
        new_cap = max(maxp // 2 - (maxp // 2) % 16, 16)
        props.append({
            "id": "prefill_chunk_tokens",
            "signal": "prefill_dominates_with_decodes_waiting",
            "score": round(prefill_share, 4),
            "knob": "scheduler.prefill_chunk_tokens",
            "overrides": {"serving": {"scheduler":
                                      {"prefill_chunk_tokens": new_cap}}},
            "reason": f"prefill holds {prefill_share:.0%} of tick time "
                      f"(p99 prefill tick "
                      f"{agg['prefill']['p99_tick_ms']:.2f} ms) with "
                      f"decodes in flight and a worst tick of {maxp} "
                      f"prefill tokens: decode latency is serialized "
                      f"behind long prompts — cap chunked prefill at "
                      f"{new_cap} tokens/tick",
            "predicted": {"counter": "max_prefill_tokens_per_tick",
                          "op": "<=", "value": new_cap,
                          "baseline": maxp,
                          "unit": "tokens"},
        })
    cur_hyst = float(cfg.get("ladder_hysteresis", 0.10))
    if sig["brownout_entries"] >= 2 and cur_hyst < 0.30:
        # brownout flapping: the ladder re-enters brownout on pressure
        # jitter — widen the descent band so one episode stays one episode
        new_hyst = round(min(cur_hyst * 2, 0.30), 3)
        props.append({
            "id": "widen_ladder_hysteresis",
            "signal": "brownout_flapping",
            "score": round(min(sig["brownout_entries"], 10) / 10.0, 4),
            "knob": "ladder_hysteresis",
            "overrides": {"serving": {"ladder_hysteresis": new_hyst}},
            "reason": f"{sig['brownout_entries']} brownout entries in one "
                      f"run at hysteresis {cur_hyst}: the ladder flaps on "
                      "pressure jitter — widen the descent band to "
                      f"{new_hyst}",
            "predicted": {"counter": "brownout_entries", "op": "<=",
                          "value": sig["brownout_entries"],
                          "baseline": sig["brownout_entries"],
                          "unit": "entries"},
        })
    props.sort(key=lambda p: (-p["score"], p["id"]))
    return props


# ---------------------------------------------------------------------------
# regression baseline (dslint/plan ratchet idiom)
# ---------------------------------------------------------------------------
def load_serve_plan_baseline(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    if data.get("version") != SERVE_PLAN_BASELINE_VERSION:
        raise ValueError(f"unsupported serve plan baseline version "
                         f"{data.get('version')!r} in {path} "
                         f"(expected {SERVE_PLAN_BASELINE_VERSION})")
    return data


def find_serve_plan_baseline(start: str) -> Optional[str]:
    """Walk up from ``start`` looking for the checked-in baseline (same
    discovery rule as dslint's / plan's)."""
    d = os.path.abspath(start)
    if os.path.isfile(d):
        d = os.path.dirname(d)
    while True:
        cand = os.path.join(d, SERVE_PLAN_BASELINE_NAME)
        if os.path.exists(cand):
            return cand
        parent = os.path.dirname(d)
        if parent == d:
            return None
        d = parent


def write_serve_plan_baseline(path: str, report: Dict[str, Any],
                              tolerance: float = 2.0,
                              min_abs_ms: float = 0.05) -> dict:
    """Record the report's per-stage tick quantiles as the new baseline,
    workload-scoped by the TRACE basename (same contract as
    ``plan_baseline.json``: discovered baselines only judge traces of
    their own workload)."""
    data = {
        "version": SERVE_PLAN_BASELINE_VERSION,
        "workload": os.path.basename(str(report.get("trace", ""))),
        "tolerance": float(tolerance),
        "min_abs_ms": float(min_abs_ms),
        "entries": {
            s: {"p50_tick_ms": report["aggregate"][s]["p50_tick_ms"],
                "p95_tick_ms": report["aggregate"][s]["p95_tick_ms"]}
            for s in STAGES},
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    return data


def check_baseline(report: Dict[str, Any], baseline: dict,
                   tolerance: Optional[float] = None
                   ) -> Tuple[List[dict], List[dict]]:
    """(regressions, stale) — the plan ratchet: a stage REGRESSES when its
    current per-tick quantile exceeds baseline * tolerance AND the
    absolute floor; an improved entry is STALE and expires only via
    ``--write-baseline``."""
    tol = float(tolerance if tolerance is not None
                else baseline.get("tolerance", 2.0))
    floor = float(baseline.get("min_abs_ms", 0.05))
    regressions, stale = [], []
    for stage, entry in sorted(baseline.get("entries", {}).items()):
        agg = report["aggregate"].get(stage)
        if agg is None:
            continue
        for metric in ("p50_tick_ms", "p95_tick_ms"):
            base = float(entry.get(metric, 0.0))
            cur = float(agg[metric])
            row = {"stage": stage, "metric": metric, "baseline_ms": base,
                   "current_ms": cur,
                   "ratio": round(cur / base, 3) if base > 0 else None}
            if cur > base * tol and (cur - base) > floor:
                regressions.append(row)
            elif base > cur * tol and (base - cur) > floor:
                stale.append(row)
    return regressions, stale


# ---------------------------------------------------------------------------
# rendering + CLI
# ---------------------------------------------------------------------------
def render(report: Dict[str, Any], top_windows: int = 8) -> str:
    out = []
    out.append(f"dstpu plan --serve — {report['source']}")
    prov = report.get("provenance") or {}
    preset = prov.get("preset", "?")
    out.append(f"preset={preset} seed={prov.get('seed', '?')} | "
               f"{report['ticks_total']} ticks, "
               f"{report['window_ms_total']:.1f} ms traced tick time, "
               f"p50 tick {report['tick_ms_p50']:.3f} ms "
               f"(windows: {report['window_mode']})")
    out.append("")
    hdr = f"{'win':>4} {'tick':>6} {'ms':>9}"
    for s in STAGES:
        hdr += f" {s[:8]:>9}"
    out.append(hdr + "   tie-out")
    out.append("-" * len(hdr))
    for w in report["windows"][:top_windows]:
        tick = w["tick"] if w["tick"] is not None else "-"
        row = f"{w['index']:>4} {tick:>6} {w['dur_us'] / 1e3:>9.3f}"
        for s in STAGES:
            row += f" {w['stages_us'][s] / 1e3:>9.3f}"
        row += f"   {w['tie_out_error'] * 100:.2f}%"
        out.append(row)
    if len(report["windows"]) > top_windows:
        out.append(f"... {len(report['windows']) - top_windows} more "
                   "windows (--top N)")
    out.append("")
    out.append(f"{'stage':<10} {'share':>7} {'p50/tick':>10} "
               f"{'p95/tick':>10} {'p99/tick':>10}")
    out.append("-" * 51)
    for s in STAGES:
        a = report["aggregate"][s]
        out.append(f"{s:<10} {a['share'] * 100:>6.1f}% "
                   f"{a['p50_tick_ms']:>9.3f}ms {a['p95_tick_ms']:>9.3f}ms "
                   f"{a['p99_tick_ms']:>9.3f}ms")
    req = report.get("requests", {})
    if req.get("levels"):
        out.append("")
        out.append("request latency from retro-spans (per ladder level)")
        for level, r in req["levels"].items():
            out.append(f"  {level:<10} n={r['count']:<5} "
                       f"ttft p50/p99 {r['ttft_p50_ms']:.2f}/"
                       f"{r['ttft_p99_ms']:.2f} ms  tpot p50/p99 "
                       f"{r['tpot_p50_ms']:.3f}/{r['tpot_p99_ms']:.3f} ms")
    inst = report.get("instants", {})
    if inst.get("ladder_edges") or inst.get("backpressure"):
        out.append("")
        out.append(f"ladder edges: {inst.get('ladder_edges')}  "
                   f"backpressure: {inst.get('backpressure')}")
    out.append("")
    if report["proposals"]:
        out.append("proposals (dominant pressure -> serving override):")
        for p in report["proposals"]:
            out.append(f"  [{p['id']}] {p['reason']}")
            out.append(f"      overrides: {json.dumps(p['overrides'])}")
            pred = p["predicted"]
            out.append(f"      predicted: {pred['counter']} {pred['op']} "
                       f"{pred['value']} {pred.get('unit', '')} (verify "
                       "with dstpu_bench_serve --verify-plan)")
    else:
        out.append("proposals: none — no pressure signal clears its rule "
                   "(the knobs fit this traffic mix)")
    return "\n".join(out)


def analyze_serve_path(path: str) -> Dict[str, Any]:
    """Load + attribute in one call (the API the tests, env_report and
    verify runner use). ``path`` is a trace dump or bench_serve report."""
    events, meta = load_input(path)
    return attribute_serve(events, source=path, meta=meta)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="dstpu plan --serve",
        description="serving-tick attribution, siege-knob regression "
                    "ledger, and proposal generation (input: a dstrace "
                    "dump or a bench_serve report with provenance)")
    parser.add_argument("input", help="dstrace Chrome-trace dump or "
                                      "bench_serve report JSON")
    parser.add_argument("--baseline", default=None,
                        help=f"baseline path (default: walk up from the "
                             f"trace for {SERVE_PLAN_BASELINE_NAME})")
    parser.add_argument("--write-baseline", action="store_true",
                        help="record this report as the new baseline "
                             "(ratchet: also how stale entries expire)")
    parser.add_argument("--tolerance", type=float, default=None,
                        help="regression factor vs baseline (default: the "
                             "factor stored in the baseline)")
    parser.add_argument("--out", default=None,
                        help="write the full plan artifact JSON here "
                             f"(env_report reads ${SERVE_PLAN_ARTIFACT_ENV} "
                             f"or ./{DEFAULT_SERVE_PLAN_ARTIFACT})")
    parser.add_argument("--json", action="store_true",
                        help="print the report as JSON instead of a table")
    parser.add_argument("--top", type=int, default=8,
                        help="ledger windows to show (default 8)")
    args = parser.parse_args(argv)

    try:
        report = analyze_serve_path(args.input)
    except PlanError as e:
        print(f"dstpu plan --serve: {e}", file=sys.stderr)
        return EXIT_UNREADABLE

    # discovery anchors at the TRACE path (workload scoping, same contract
    # as plan_baseline.json); pass --baseline to compare across workloads
    trace_path = report["trace"]
    bl_path = args.baseline or find_serve_plan_baseline(trace_path)
    regressions, stale = [], []
    effective_tol = args.tolerance if args.tolerance is not None else 2.0
    trace_workload = os.path.basename(trace_path)
    if args.write_baseline:
        trace_dir = os.path.dirname(os.path.abspath(trace_path))
        target = bl_path or os.path.join(trace_dir,
                                         SERVE_PLAN_BASELINE_NAME)
        if args.baseline is None and os.path.exists(target):
            try:    # never clobber a DISCOVERED baseline of another
                existing_wl = load_serve_plan_baseline(target) \
                    .get("workload")
            except (OSError, ValueError):
                existing_wl = None
            if existing_wl and existing_wl != trace_workload:
                redirected = os.path.join(trace_dir,
                                          SERVE_PLAN_BASELINE_NAME)
                if os.path.abspath(redirected) == os.path.abspath(target):
                    print(f"# refusing --write-baseline: {target} ratchets "
                          f"workload {existing_wl!r} — pass --baseline "
                          "PATH to overwrite it deliberately",
                          file=sys.stderr)
                    target = None
                else:
                    print(f"# note: {target} ratchets workload "
                          f"{existing_wl!r} — starting this workload's "
                          f"baseline at {redirected} instead",
                          file=sys.stderr)
                    target = redirected
        if target is not None:
            if args.tolerance is None and os.path.exists(target):
                try:    # ratchet rewrite: keep the factor the team chose
                    effective_tol = float(load_serve_plan_baseline(target)
                                          .get("tolerance", 2.0))
                except (OSError, ValueError):
                    pass
            write_serve_plan_baseline(target, report,
                                      tolerance=effective_tol)
            print(f"# serve plan baseline written -> {target}",
                  file=sys.stderr)
        bl_path = target
    elif bl_path:
        try:
            baseline = load_serve_plan_baseline(bl_path)
        except (OSError, ValueError) as e:
            print(f"dstpu plan --serve: bad baseline {bl_path}: {e}",
                  file=sys.stderr)
            return EXIT_UNREADABLE
        bl_workload = baseline.get("workload")
        if args.baseline is None and bl_workload \
                and bl_workload != trace_workload:
            print(f"# note: discovered baseline {bl_path} is for workload "
                  f"{bl_workload!r}, not {trace_workload!r} — comparison "
                  "skipped (pass --baseline to compare anyway, or "
                  "--write-baseline to start ratcheting this workload)",
                  file=sys.stderr)
            bl_path = None
        else:
            regressions, stale = check_baseline(report, baseline,
                                                tolerance=args.tolerance)
            effective_tol = args.tolerance if args.tolerance is not None \
                else float(baseline.get("tolerance", 2.0))
    report["baseline"] = {"path": bl_path, "regressions": regressions,
                          "stale": stale}

    # the tie-out contract is CHECKED, not assumed (attribution.py idiom)
    violations = [w["index"] for w in report["windows"]
                  if w["tie_out_error"] > TIE_OUT_TOLERANCE]
    report["tie_out_violations"] = violations
    for idx in violations:
        w = report["windows"][idx]
        print(f"WARNING: tick window {idx} over-attributes "
              f"{w['tie_out_error'] * 100:.1f}% of its span "
              f"(> {TIE_OUT_TOLERANCE * 100:.0f}% tolerance) — "
              "overlapping or skewed spans; treat its ledger row as "
              "suspect", file=sys.stderr)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render(report, top_windows=args.top))
        for r in regressions:
            print(f"REGRESSION: {r['stage']} {r['metric']} "
                  f"{r['baseline_ms']:.3f} -> {r['current_ms']:.3f} ms "
                  f"({r['ratio']}x, tolerance {effective_tol}x) vs "
                  f"{bl_path}", file=sys.stderr)
        for r in stale:
            print(f"stale baseline entry (improved): {r['stage']} "
                  f"{r['metric']} {r['baseline_ms']:.3f} -> "
                  f"{r['current_ms']:.3f} ms — re-run with "
                  f"--write-baseline to ratchet", file=sys.stderr)
    return EXIT_REGRESSION if regressions else EXIT_OK


if __name__ == "__main__":
    sys.exit(main())
