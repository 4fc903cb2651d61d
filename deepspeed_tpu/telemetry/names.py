"""Canonical trace-name registry — THE one place a span/instant/counter
name is declared.

Until dslint v2 the emitters (engine, server, fleet, chaos, comm guard)
and the offline consumers (``attribution.py`` / ``serve_attribution.py``
/ ``crossrank.py`` stage tables, the plan rules, the bench gates) agreed
on names one hand-written test at a time — renaming an emitted span
silently dropped it out of the exclusive-stage ledgers and every
downstream share went to ``residual``. Now:

* every name a ``Tracer.span/instant/counter/complete`` call emits as a
  literal MUST appear in :data:`TRACE_NAMES` (rule **DS007**; dynamic
  f-string names must start with a :data:`DYNAMIC_PREFIXES` entry), and
* the offline stage tables derive their name constants FROM this module,

so a rename that touches only one side is a lint finding, not a silent
attribution hole.

Contract: this module is **stdlib-only pure data** and must stay loadable
standalone (``importlib`` file-load, no package import) — the offline
consumers run on jax-less hosts and load it from the sibling path under
``sys.modules["dstpu_trace_names"]``.

Adding a name: add the ``name -> (kinds,)`` entry here (kinds from
``span``/``instant``/``counter``/``complete``), emit it, and — if an
offline sweep should attribute it — extend the relevant stage constant
below. ``python bin/dslint deepspeed_tpu`` confirms both sides agree.
"""

from typing import Dict, Tuple

#: every literal trace name the package emits, mapped to the event kinds
#: it may be emitted as. DS007 flags an emitted literal that is missing
#: here, and a registered name emitted as an unregistered kind.
TRACE_NAMES: Dict[str, Tuple[str, ...]] = {
    # -- training engine ---------------------------------------------------
    "engine/train_step": ("span",),
    "engine/dispatch": ("span",),
    "engine/drain": ("span",),              # DispatchRing's drain span
    "engine/steps_reconciled": ("complete",),
    "engine/overflow_step": ("instant",),
    "comm/h2d": ("span",),
    "comm/overlap": ("complete",),
    "ckpt/save": ("span",),
    "ckpt/load": ("span",),
    "prefetch/next": ("span",),
    "prefetch/stage": ("span",),
    "xla/compile": ("instant",),
    # one collection of the interpreter's, "start" to "stop", stamped by the
    # tracer's own entry in ``gc.callbacks`` on a track of its own
    # (``tracer.HOST_GC_TID``): `generation`, `collected`, the `thread` it
    # ran on, and the youngest generation's collections shorter than 1 ms
    # since the last one emitted (`gen0`, `gen0_s`)
    "host/gc": ("complete",),
    # -- memory telemetry --------------------------------------------------
    "mem/oom": ("instant",),
    "mem/see_memory_usage": ("instant",),
    "mem/hbm_bytes_in_use": ("counter",),
    "mem/hbm_peak_bytes": ("counter",),
    "mem/hbm_bytes_limit": ("counter",),
    "mem/host_rss_bytes": ("counter",),
    # -- collective guard / membership ------------------------------------
    "comm/init_retry": ("instant",),
    "comm/init_wedge": ("instant",),
    "comm/op_failed": ("instant",),
    "comm/wedge": ("instant",),
    "comm/straggler": ("instant",),
    # -- resilience --------------------------------------------------------
    "resilience/bad_step": ("instant",),
    "resilience/lr_backoff": ("instant",),
    "resilience/quarantine": ("instant",),
    "resilience/comm_fault": ("instant",),
    "resilience/preempt_signal": ("instant",),
    "resilience/watchdog_flag": ("instant",),
    # -- chaos drills ------------------------------------------------------
    "chaos/stall": ("complete",),
    "chaos/serve_slow_tick": ("complete",),
    "chaos/ckpt_io_fail": ("instant",),
    "chaos/comm_delay": ("instant",),
    "chaos/comm_wedge": ("instant",),
    "chaos/die": ("instant",),
    "chaos/nan": ("instant",),
    "chaos/oom": ("instant",),
    "chaos/replica_kill": ("instant",),
    "chaos/serve_kv_pressure": ("instant",),
    "chaos/serve_poison": ("instant",),
    # -- elasticity --------------------------------------------------------
    "elastic/peer_lost": ("instant",),
    "elastic/regrow": ("instant",),
    "elastic/shrink_refused": ("instant",),
    "elastic/shrink_planned": ("instant",),
    "elastic/reshard": ("instant",),
    # -- serving tick ------------------------------------------------------
    "serve/tick": ("complete",),
    "serve/engine_step": ("span",),
    "serve/admit": ("span",),
    "serve/demote": ("span",),
    "serve/promote": ("span",),
    "serve/drain": ("span",),               # dumps older than the parts
    # the drain stage's three parts and the tick's tail (server retro-spans)
    "serve/drain_expire": ("complete",),
    "serve/drain_fanout": ("complete",),
    "serve/drain_reap": ("complete",),
    "serve/bookkeep": ("complete",),
    # the loop's no-work wait, from the end of the last thing it did to the
    # start of the next tick that stamps a ``serve/tick``, in pieces of at
    # most 0.25 s: `polls` (wake-ups in the piece), `queued` and `inflight`
    # at the last of them, `woke` ("submit" or "timeout"). It lies outside
    # every ``serve/tick`` and has no stage in SERVE_STAGE_OF
    "serve/idle": ("complete",),
    # stamped by the engine once the tick's collection is over, so that
    # they can carry what it read: `ahead` (1: dispatched while the step
    # before was still pending on the device, 0: nothing was), `starved`
    # beside it where `ahead` is 1 (1: that pending step's tokens were
    # already there when this one was dispatched, so the device had run dry
    # for want of the host; 0: it was still running), and on the
    # tick's decode span, or its last chunk's where it dispatched no decode
    # program, `rows_dropped` (rows whose sequence had ended by the time
    # their token was read) beside the STEP_COUNTER_ARGS. A tick whose plan
    # holds a chunk dispatches none: its decode rows ride in its last
    # chunk's program, that chunk's span says how many (`fused_rows`), and
    # the tick stamps no `serve/step_decode`, whose `batch` and `ctx_tokens`
    # are of `decode_step_g`'s kernels alone. Every span of a tick carries
    # the number of the host loop's tick it lies in, whichever step its
    # wait and commit are for
    "serve/step_prefill": ("complete",),
    "serve/step_decode": ("complete",),
    "serve/prefill_chunk": ("complete",),
    # the phases of one engine step, where the work happens: live spans,
    # mirrored into the profiler's trace (tracer.py), each carrying `tick`
    "serve/plan": ("span",),
    "serve/decode_build": ("span",),
    "serve/decode_dispatch": ("span",),
    "serve/decode_wait": ("span",),
    "serve/decode_commit": ("span",),
    "serve/step_finish": ("span",),         # the step's tail: no stage
    "serve/queued": ("complete",),
    "serve/prefill": ("complete",),
    "serve/decode": ("complete",),
    "serve/kv_bytes": ("counter",),
    # blocks held by kind of page a tick, and their bytes: `kv_held_bytes`,
    # and by kind `kv_full_bytes` and `kv_window_bytes`, each from a block of
    # the kind's own pool (its KV heads, its key and value rows as stored);
    # over a cache some of whose layers keep a recurrent state, and only
    # there, `state_slots_held` (the sequences that hold a slot) and
    # `kv_state_bytes` (what their slots hold, whatever their lengths)
    "serve/kv_pages": ("counter",),
    "serve/tick_stage_share": ("counter",),
    "serve/kv_tier": ("counter",),
    "serve/prefix_cache": ("counter",),
    "serve/backpressure": ("instant",),
    "serve/degraded": ("instant",),
    "serve/evicted": ("instant",),
    "serve/kv_demote": ("instant",),
    "serve/kv_promote": ("instant",),
    "serve/kv_recalibrate": ("instant",),
    "serve/kv_alias": ("instant",),         # one per compiled step program
    "serve/kv_drift": ("instant",),
    "serve/ladder": ("instant",),
    "serve/prefix_evict": ("instant",),
    "serve/prefix_handoff_adopt": ("instant",),
    "serve/prefix_handoff_export": ("instant",),
    "serve/quarantine": ("instant",),
    "serve/recovered": ("instant",),
    "serve/step_fault": ("instant",),
    "serve/flight_dump": ("instant",),
    # -- per-request tracing (trace_id-scoped; reqtrace.py stitches) -------
    "req/queue": ("complete",),
    "req/prefill": ("complete",),
    "req/decode": ("complete",),
    "req/reroute": ("complete",),
    "req/wall": ("complete",),
    # -- fleet router ------------------------------------------------------
    "fleet/poll_tick": ("span",),
    "fleet/rotation": ("counter",),
    "fleet/load": ("counter",),
    "fleet/handoff": ("instant",),
    "fleet/out_of_rotation": ("instant",),
    "fleet/replica_lost": ("instant",),
    "fleet/replica_relaunched": ("instant",),
    "fleet/request_lost": ("instant",),
    "fleet/reroute": ("instant",),
    "fleet/retire": ("instant",),
    "fleet/scale_out": ("instant",),
    "fleet/spill": ("instant",),
    "fleet/flight_recovered": ("instant",),
}

#: f-string names are allowed when their literal head starts with one of
#: these (per-op comm records, per-state request transitions); everything
#: else dynamic is a DS007 finding. Literal names never get prefix
#: leniency — they must be registered above.
DYNAMIC_PREFIXES: Tuple[str, ...] = ("comm/", "serve/")

# ---------------------------------------------------------------------------
# canonical constants the offline stage tables consume (attribution.py /
# serve_attribution.py / crossrank.py file-load this module standalone)
# ---------------------------------------------------------------------------
TRAIN_DISPATCH_NAMES: Tuple[str, ...] = ("engine/dispatch",
                                         "engine/train_step")
TRAIN_RECONCILE_NAME = "engine/steps_reconciled"
TRAIN_DRAIN_NAME = "engine/drain"
COMM_H2D_NAME = "comm/h2d"
COMM_OVERLAP_NAME = "comm/overlap"
COMM_PREFIX = "comm/"
CKPT_PREFIX = "ckpt/"
PREFETCH_PREFIX = "prefetch/"

HBM_IN_USE_COUNTER = "mem/hbm_bytes_in_use"
HBM_PEAK_COUNTER = "mem/hbm_peak_bytes"
HBM_LIMIT_COUNTER = "mem/hbm_bytes_limit"

SERVE_TICK_NAME = "serve/tick"

#: serving stage table: span name -> exclusive-sweep stage key. The
#: ``serve_attribution`` priorities live next to the sweep; the NAMES
#: live here so renaming an emitter trips DS007 instead of silently
#: reattributing the stage to residual.
SERVE_STAGE_OF: Dict[str, str] = {
    "serve/admit": "admission",
    "serve/step_prefill": "prefill",
    # per-chunk sub-spans nest inside step_prefill when chunked prefill
    # is on — same stage, so the exclusive sweep still ties out
    "serve/prefill_chunk": "prefill",
    "serve/step_decode": "decode",
    # the decode phases nest inside step_decode (serve/decode_wait also
    # inside a prompt's last prefill chunk, where the higher-priority
    # prefill stage owns the time): same stage, same sums. serve/plan,
    # serve/step_finish and serve/bookkeep have no stage: engine_step's and
    # the tick's own time outside the stages stays residual
    "serve/decode_build": "decode",
    "serve/decode_dispatch": "decode",
    "serve/decode_wait": "decode",
    "serve/decode_commit": "decode",
    "serve/demote": "demote",
    "serve/promote": "promote",
    "serve/drain": "drain",
    "serve/drain_expire": "drain",
    "serve/drain_fanout": "drain",
    "serve/drain_reap": "drain",
}

#: ``jax.named_scope`` names of the served step programs: what an operation's
#: ``tf_op`` holds in the device trace, and what the benchmark's per-layer
#: readers match (``generic_decode.py``, ``modules.py``, ``kv_cache.py``).
#: The ``attn/latent_*`` four are a latent (MLA) cache's: the low-rank
#: projections and the fold, the row's write, the paged decode kernel with
#: the value unfold, a chunk's gather, up-projection and prefill kernel
#: (the last two, and ``moe/experts``, through ``SERVED_LEAF_SCOPES`` below).
#: ``attn/full`` and ``attn/window`` hold a layer's write and paged attention
#: where a cache keeps pages by layer kind (whatever heads and widths a kind
#: states, with or without sinks), ``attn/gate`` a per-head output
#: gate, ``hc/pre``, ``hc/post`` and ``hc/head`` the mixing of several
#: residual streams round a sublayer (``inference/v2/hyper_connection.py``),
#: the ``ssm/*`` six a state-space (Mamba-2) mixer: its first projection,
#: the causal convolution behind the slot's tail, a prefill chunk's
#: closed-form scan from the slot's state, a decode batch's in-place update
#: of each row's state (``kv_cache._StateSlots``), the gated norm, the
#: second projection; the ``conv/*`` three a gated short convolution (LFM2):
#: the norm, the first projection and the gate before the convolution, the
#: tail's read, the taps' sum and the tail's write in a chunk and a decode
#: batch alike (``kv_cache._TailSlots``), the gate behind the convolution and
#: the second projection; ``attn/qk_norm`` the norm a head of q and of k
#: before rope
SERVED_SCOPES: Tuple[str, ...] = (
    "embed", "attn/qkv", "attn/kv_write", "attn/paged", "attn/out",
    "attn/full", "attn/window", "attn/gate", "attn/latent_q", "attn/latent_write", "attn/latent_paged",
    "attn/latent_prefill", "mlp", "moe/router", "moe/experts", "moe/shared",
    "lm_head", "sample", "hc/pre", "hc/post", "hc/head",
    "ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/update", "ssm/norm",
    "ssm/out_proj", "conv/in_proj", "conv/shift", "conv/out_proj",
    "attn/qk_norm")

#: the leaves of the three scopes above that hold several kinds of work.
#: The code opens the LEAF, by its whole name (``moe/grouped_experts.py``,
#: ``modules._routed_sum``, ``kv_cache._latent_prefill_attn`` and
#: ``_latent_paged_attn``), and never the parent alone: a callee under a
#: ``jit`` of its own is lowered without its caller's names, so only a whole
#: name stays one piece of an operation's ``tf_op``
#: (``jit(prefill_chunk_g)/jit(_routed_sum)/moe/experts/sort/...``). Every
#: operation of a parent is under exactly one of its leaves, so whoever
#: matches the parent's name reads what it read, and the leaves' seconds sum
#: to the parent's. A fusion carries one name, its root's.
SERVED_LEAF_SCOPES: Dict[str, str] = {
    "moe/experts/sort": "which assignments stay (`keep`), their key, the "
                        "`argsort`, the rows on each expert and the layer's "
                        "counts made from them",
    "moe/experts/gather": "`xs = h[order // k]`: the [T*K, D] rows into "
                          "expert order",
    "moe/experts/matmul": "the weights' casts, the first product with its "
                          "activation and the second product (the Pallas "
                          "calls keep their names `grouped_matmul*`)",
    "moe/experts/combine": "the zeroing of the rows past the groups, the "
                           "inverse `argsort` and the gather back, the "
                           "weights' `where`, the `einsum` over the top-k",
    "attn/latent_prefill/gather": "the padded table, `pool[layer, table]`, "
                                  "the reshape and the cuts to `keys` rows, "
                                  "their compressed part and their rope part",
    "attn/latent_prefill/up_proj": "the two `einsum`s from `ckv`: every "
                                   "head's keys and values of the whole "
                                   "bucket",
    "attn/latent_prefill/kernel": "the transposes round it and "
                                  "`_latent_prefill_call` (the reference on "
                                  "the gather path)",
    "attn/latent_paged/kernel": "the layer's offset into the tables and "
                                "`_latent_paged_call` (the reference on the "
                                "gather path)",
    "attn/latent_paged/unfold": "the value up-projection `einsum` behind "
                                "the kernel",
}

#: ``jax.named_scope`` names of the trained step (``models/llama.py``,
#: ``runtime/engine.py``): the output head with the loss behind it, forward
#: and backward, and the optimizer's update. A layer's work carries its flax
#: modules' names instead (``.../layer_<i>/attn/...``, ``.../mlp/...``), in
#: the forward pass, the recomputed forward and the backward pass alike
TRAINED_SCOPES: Tuple[str, ...] = ("lm_head_loss", "optimizer")

#: counts a step program computes on the device where its policy's layers
#: count (``generic_decode.py``), in the order of the int32 vector it hands
#: out: token-expert pairs of the step, experts with at least one row, and
#: rows of the tiles the grouped matmul visited (``grouped_matmul.py`` under
#: ``ops/pallas``; ``expert_rows`` over it is the tiles' fill, 1 where
#: ``jax.lax.ragged_dot`` runs and there are no tiles to count), and the
#: assignments left out because their expert is held on another chip (0
#: where a layer's experts are held whole), each summed over the expert
#: layers. The engine reads them with the sampled tokens of the step they
#: belong to and puts them on the ``serve/step_decode`` of the tick that
#: read them (its last ``serve/prefill_chunk`` where it decoded nothing) as
#: args of these names; a chunk that ends no prompt is not waited for, so
#: its counts ride with the next step's that is
STEP_COUNTER_ARGS: Tuple[str, ...] = ("expert_rows", "experts_touched",
                                      "expert_tile_rows",
                                      "expert_rows_absent")

#: two host counts beside the ones they divide, from static shapes, stamped
#: only while the tracer is on. ``expert_rows_gathered`` beside
#: ``expert_rows``, on the same span and of the same steps (a chunk that
#: ends no prompt hands both on to the next span that waits): the rows the
#: experts' wrapper sorts, gathers and gathers back, ``ids.size`` (the
#: program's rows, padding and a chunk program's decode half among them, x
#: top-k) summed over the expert layers; ``expert_rows`` over it is the
#: share of them a grouped matmul visits. ``latent_keys_gathered`` beside
#: ``latent_panels`` on a ``serve/prefill_chunk`` over a latent pool: the
#: rows one layer's call gathers and up-projects for the chunk
#: (``latent_attention.prefill_keys`` of its context bucket); ``start`` +
#: ``tokens`` over it is the share the chunk's rows can see
GATHERED_ROWS_ARG = "expert_rows_gathered"
LATENT_KEYS_ARG = "latent_keys_gathered"

#: two host counts, one a span, of what a step program writes to the paged
#: pool, from what the tick knows (stamped with the span's other args):
#: ``kv_rows_written`` on a ``serve/prefill_chunk`` is the chunk's ``tokens``
#: plus the decode rows that rode in its program, on a ``serve/step_decode``
#: the batch; ``kv_rows_by_page`` is the part of it that went in a page at a
#: time (``kv_cache.write_chunk_pages``): the chunk's ``tokens`` where the
#: chunk's bucket is a block or more over pages that are not fp8 scaled
#: (``BlockedKVCache.chunk_by_page``), 0 on a decode span, whose rows lie in
#: as many pages as sequences and keep the row scatter. A state kind's slot
#: is no row of the pool and counts in neither
KV_ROWS_WRITTEN_ARG = "kv_rows_written"
KV_ROWS_BY_PAGE_ARG = "kv_rows_by_page"

#: per-request tracing namespace (reqtrace.py file-loads this module
#: standalone, same contract as the tables above). Spans carrying a
#: ``trace_id`` arg under REQ_PREFIX are the stitch join; REQ_STAGE_OF
#: maps each lifecycle span to its timeline stage; REQ_WALL_NAME is the
#: router-side envelope every replica-side span must fit inside (the
#: tie-out denominator); REQ_TRACE_ARG is the one arg key the join uses.
REQ_PREFIX = "req/"
REQ_TRACE_ARG = "trace_id"
REQ_WALL_NAME = "req/wall"
REQ_REROUTE_NAME = "req/reroute"
REQ_STAGE_OF: Dict[str, str] = {
    "req/queue": "queue",
    "req/prefill": "prefill",
    "req/decode": "decode",
    "req/reroute": "reroute",
}
