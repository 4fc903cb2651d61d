"""The training engine.

Reference analog: ``DeepSpeedEngine`` (``deepspeed/runtime/engine.py:182``) — the object
returned by ``initialize()`` that owns distributed setup, precision, partitioning,
optimizer, step loop, and checkpointing.

TPU-native redesign (SURVEY.md §7): instead of wrapping an eager module with hooks, the
engine compiles **one fused train step** — forward + backward + (at the gradient
accumulation boundary) optimizer update — under ``jax.jit`` with explicit
``NamedSharding``s implementing the configured ZeRO stage over the mesh's ``fsdp``
axis. Gradient accumulation over microbatches is a ``lax.scan`` inside the same
compiled step, so XLA overlaps the grad reduce-scatter of microbatch *i* with the
compute of *i+1* (the hand-written IPG-bucket overlap of ``stage_1_and_2.py:898``
comes out of the compiler for free).

The reference's ``forward``/``backward``/``step`` three-call protocol is kept as a
compatibility shim: ``forward`` runs a jitted value-and-grad and caches the grads,
``backward`` accumulates them into a device-resident buffer, ``step`` applies the
update at the accumulation boundary — the idiomatic entry point is ``train_batch``.
"""

import collections
import functools
import os
import time
import weakref
from typing import Any, Callable, Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.comm.comms_logging import get_comms_logger
from deepspeed_tpu.config.config import DeepSpeedTPUConfig
from deepspeed_tpu.ops.optimizers import build_optimizer
from deepspeed_tpu.runtime import precision
from deepspeed_tpu.runtime.lr_schedules import build_schedule, constant_lr
from deepspeed_tpu.runtime.zero.partition import (
    build_opt_state_shardings,
    build_param_shardings,
    build_secondary_shardings,
)
from deepspeed_tpu.telemetry.compiles import watch_jit
from deepspeed_tpu.telemetry.tracer import get_tracer
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.runtime.dataloader import PrefetchLoader, StagedBatch
from deepspeed_tpu.runtime.sched import DispatchRing, StagedPrefetcher
from deepspeed_tpu.utils.timer import (
    BACKWARD_GLOBAL_TIMER,
    FORWARD_GLOBAL_TIMER,
    STEP_GLOBAL_TIMER,
    TRAIN_BATCH_DISPATCH_TIMER,
    TRAIN_BATCH_TIMER,
    SynchronizedWallClockTimer,
    ThroughputTimer,
)

import optax


class EngineState(NamedTuple):
    """The jit-carried training state: the analog of the engine's module params +
    optimizer internals + loss scaler, as one donated pytree."""
    step: jnp.ndarray                       # global optimizer step (int32)
    params: Any                             # fp32 master params (ZeRO-sharded)
    opt_state: Any                          # optax state (ZeRO-sharded)
    loss_scale: precision.LossScaleState
    skipped_steps: jnp.ndarray              # overflow-skipped step count


class StepOutput(NamedTuple):
    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    lr: jnp.ndarray
    overflow: jnp.ndarray


def _as_apply_fn(model) -> Callable:
    """Accept a flax Module (init/apply), or a bare callable
    ``apply_fn(params, batch, rng) -> loss | (loss, aux)``."""
    if hasattr(model, "apply") and callable(model.apply):
        def apply_fn(params, batch, rng):
            kwargs = {}
            if rng is not None:
                kwargs["rngs"] = {"dropout": rng}
            return model.apply({"params": params}, batch, **kwargs)
        return apply_fn
    if callable(model):
        return model
    raise TypeError(f"model must be a flax Module or callable, got {type(model)}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _qwz_regather(leaf, sec_sharding, scale_sharding):
    """ZeRO++ qwZ re-layout: symmetric per-row int8 quantize, constrain the int8
    codes + fp32 scales to the secondary (inner-group) sharding — so the
    cross-``fsdp_out`` gather moves ~¼ the bytes of the compute dtype — then
    dequantize (reference: quantized-weights allgather, CUDAQuantizer
    partition_parameters.py:761). custom_vjp gives the straight-through
    gradient (identity) without materializing a full-precision gather of the
    original leaf on the forward path."""
    absmax = jnp.max(jnp.abs(leaf.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(leaf.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    q = jax.lax.with_sharding_constraint(q, sec_sharding)
    scale = jax.lax.with_sharding_constraint(scale, scale_sharding)
    return (q.astype(jnp.float32) * scale).astype(leaf.dtype)


def _qwz_fwd(leaf, sec_sharding, scale_sharding):
    return _qwz_regather(leaf, sec_sharding, scale_sharding), None


def _qwz_bwd(sec_sharding, scale_sharding, _, g):
    return (g,)


_qwz_regather.defvjp(_qwz_fwd, _qwz_bwd)


class DeepSpeedTPUEngine:
    def __init__(self,
                 model,
                 config: DeepSpeedTPUConfig,
                 params: Optional[Any] = None,
                 loss_fn: Optional[Callable] = None,
                 mesh: Optional[Mesh] = None,
                 example_batch: Optional[Any] = None,
                 tensor_rules: Optional[Callable] = None,
                 batch_spec: Optional[Any] = None,
                 seed: int = 0,
                 lr_scheduler: Optional[Callable] = None,
                 client_optimizer: Optional[Any] = None):
        self.config = config
        self.model = model
        self.loss_fn = loss_fn
        self.accelerator = get_accelerator()
        if config.debug_nans:
            if config.fp16.enabled:
                log_dist("debug_nans ignored with fp16: transient overflows "
                         "are expected and handled by the loss scaler",
                         ranks=[0])
            else:
                # NOTE: jax_debug_nans is process-global by construction
                jax.config.update("jax_debug_nans", True)
                log_dist("debug_nans: aborting at the first NaN-producing op "
                         "(process-global jax flag)", ranks=[0])
        elif config.fp16.enabled and jax.config.jax_debug_nans:
            # another engine in this process owns the global flag — don't
            # silently revoke its NaN protection; fp16 loss scaling here WILL
            # trip it on expected transient overflows, so the user must pick one
            log_dist("WARNING: jax_debug_nans is enabled process-globally by "
                     "another engine; this fp16 engine's overflow-skip "
                     "produces transient inf/NaN that will abort under it. "
                     "Disable debug_nans or fp16.", ranks=[0])

        # --- hierarchical ZeRO world (MiCS / ZeRO++ hpZ) ---------------------
        # Both split the ZeRO world into (fsdp_out x fsdp): MiCS shards within
        # the inner group and replicates across groups (mics.py:64); hpZ keeps
        # the full shard for memory but constrains the compute copy to the
        # inner group (partition_parameters.py:1664).
        zc = config.zero_config
        self._mics = zc.mics_shard_size is not None and zc.mics_shard_size > 0
        self._hpz = int(zc.zero_hpz_partition_size or 1)
        if self._mics and self._hpz > 1:
            raise ValueError(
                "mics_shard_size and zero_hpz_partition_size are mutually "
                "exclusive: MiCS already replicates across shard groups, so an "
                "hpZ secondary shard would be a no-op")
        inner = zc.mics_shard_size if self._mics else (self._hpz if self._hpz > 1 else 0)
        if inner and mesh is None:
            if config.mesh.fsdp == -1:
                raise ValueError("MiCS/hpZ needs an explicit mesh.fsdp size to split")
            if config.mesh.fsdp_outer == 1 and config.mesh.fsdp > inner:
                if config.mesh.fsdp % inner:
                    raise ValueError(
                        f"fsdp={config.mesh.fsdp} not divisible by shard group {inner}")
                config.mesh.fsdp_outer = config.mesh.fsdp // inner
                config.mesh.fsdp = inner
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh(config.mesh)
        mesh_lib.set_global_mesh(self.mesh)
        if inner and self.mesh.shape.get("fsdp", 1) != inner \
                and self.mesh.shape.get("fsdp_out", 1) == 1:
            log_dist(f"MiCS/hpZ shard group {inner} != mesh fsdp "
                     f"{self.mesh.shape['fsdp']}; using mesh layout as-is", ranks=[0])

        self.dp_world_size = mesh_lib.get_data_parallel_world_size(self.mesh)
        config.resolve_batch_sizes(self.dp_world_size)
        self.train_batch_size = config.train_batch_size
        self.micro_batch_size = config.train_micro_batch_size_per_gpu
        self.gradient_accumulation_steps = config.gradient_accumulation_steps
        log_dist(f"engine: {config!r} mesh={dict(self.mesh.shape)}", ranks=[0])

        if config.comms_logger.enabled:
            get_comms_logger().configure(enabled=True,
                                         verbose=config.comms_logger.verbose,
                                         prof_all=config.comms_logger.prof_all,
                                         prof_ops=config.comms_logger.prof_ops)

        self.compute_dtype = config.precision_dtype
        self.zero_stage = config.zero_config.stage
        self._apply_fn = _as_apply_fn(model)
        self._rng = jax.random.PRNGKey(seed)

        # --- LR schedule -----------------------------------------------------
        if lr_scheduler is not None:
            self.lr_schedule = lr_scheduler
        elif config.scheduler and config.scheduler.type:
            sched_params = dict(config.scheduler.params)
            self.lr_schedule = build_schedule(config.scheduler.type, sched_params)
        else:
            base_lr = (config.optimizer.params.get("lr", 1e-3)
                       if config.optimizer else 1e-3)
            self.lr_schedule = constant_lr(lr=base_lr)

        # --- optimizer -------------------------------------------------------
        # A client optimizer (optax GradientTransformation) is authoritative, as in
        # the reference (engine._configure_optimizer prefers the client optimizer).
        if client_optimizer is not None:
            if not (hasattr(client_optimizer, "init") and hasattr(client_optimizer, "update")):
                raise TypeError("client optimizer must be an optax GradientTransformation "
                                f"(has init/update), got {type(client_optimizer)}")
            self.tx = client_optimizer
        else:
            opt_type = config.optimizer.type if config.optimizer else "adamw"
            opt_params = dict(config.optimizer.params) if config.optimizer else {}
            self.tx = build_optimizer(opt_type, opt_params, lr_schedule=self.lr_schedule)

        # batch sharding: leading dim over (data, fsdp) unless caller overrides
        self.batch_spec = batch_spec if batch_spec is not None \
            else PartitionSpec(mesh_lib.batch_axes(self.mesh))
        self.batch_sharding = NamedSharding(self.mesh, self.batch_spec)

        # --- ZeRO-Infinity parameter offload ---------------------------------
        # Params live on host/NVMe and stream through HBM layer-group by
        # layer-group (runtime/param_offload.py; reference
        # partitioned_param_swapper.py:37). A non-"none" offload_param either
        # takes effect here or RAISES — never parses-and-ignores.
        self._param_offload = None
        _pcfg = config.zero_config.offload_param
        if _pcfg.device != "none":
            from deepspeed_tpu.runtime.param_offload import (
                ParamOffloadTrainer, validate_param_offload)
            # fail fast BEFORE host param init (which may allocate tens of GB)
            validate_param_offload(config, model)
            if client_optimizer is not None:
                raise ValueError(
                    "offload_param requires a config-typed optimizer (the "
                    "update runs in the fused host kernel, not optax)")
            if params is None:
                if example_batch is None:
                    raise ValueError("example_batch required to init a flax "
                                     "Module")
                self._rng, init_rng = jax.random.split(self._rng)
                params = self._host_init_params(model, example_batch, init_rng)
            params = jax.tree.map(lambda x: np.asarray(x), params)
            scalar_sharding = NamedSharding(self.mesh, PartitionSpec())
            self.param_shardings = None
            self.opt_state_shardings = ()
            self.state = EngineState(
                step=jax.device_put(jnp.int32(0), scalar_sharding),
                params=(),
                opt_state=(),
                loss_scale=jax.device_put(
                    precision.init_loss_scale(config.fp16), scalar_sharding),
                skipped_steps=jax.device_put(jnp.int32(0), scalar_sharding),
            )
            self.state_shardings = None
            self._param_offload = ParamOffloadTrainer(
                model, config, params, self.mesh, self.batch_sharding,
                self.lr_schedule, tensor_rules=tensor_rules)
            params = None      # host copy now owned by the trainer's masters
            # checkpoint interop: host masters are the authoritative weights
            self._offload = self._param_offload.opt
            self._offload_grad_fn = None
            self._offload_apply_fn = None
            self._params_treedef = self._param_offload.treedef

        # --- parameter init + sharding --------------------------------------
        if self._param_offload is not None:
            pass
        elif params is None:
            if not hasattr(model, "init"):
                raise ValueError("pass `params` or a flax Module with .init")
            if example_batch is None:
                raise ValueError("example_batch required to init a flax Module")
            self._rng, init_rng = jax.random.split(self._rng)
            variables = jax.eval_shape(lambda r: model.init(r, example_batch), init_rng)
            params_shape = variables["params"]
            self.param_shardings = build_param_shardings(
                params_shape, self.mesh, self.zero_stage, tensor_rules,
                mics=self._mics)

            def _init(r):
                return model.init(r, example_batch)["params"]
            params = jax.jit(_init, out_shardings=self.param_shardings)(init_rng)
        else:
            self.param_shardings = build_param_shardings(
                params, self.mesh, self.zero_stage, tensor_rules,
                mics=self._mics)
            params = jax.device_put(
                jax.tree.map(lambda x: np.asarray(x), params), self.param_shardings)

        if self._param_offload is None:
            # fp32 master weights (reference: FP16_Optimizer / BF16_Optimizer)
            params = jax.tree.map(
                lambda x: x.astype(jnp.float32)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

            # --- optimizer-state offload tier (ZeRO-Offload / Infinity) ------
            # Constructed BEFORE device state: under offload the device holds
            # only compute-dtype param shadows — no fp32 masters, no optimizer
            # moments in HBM (that is the point of the tier; reference keeps
            # fp16 shards on device and fp32 masters + moments on host).
            self._offload = None
            self._offload_grad_fn = None
            self._offload_apply_fn = None
            offload_cfg = config.zero_config.offload_optimizer
            if offload_cfg.device in ("cpu", "nvme"):
                from deepspeed_tpu.runtime.offload import HostOffloadOptimizer
                host_leaves = [np.asarray(jax.device_get(p), np.float32)
                               for p in jax.tree.leaves(params)]
                opt_type = config.optimizer.type if config.optimizer else "adamw"
                self._offload = HostOffloadOptimizer(
                    host_leaves, opt_type,
                    dict(config.optimizer.params) if config.optimizer else {},
                    offload_cfg)
                self._params_treedef = jax.tree_util.tree_structure(params)
                params = jax.jit(
                    lambda p: precision.cast_to_compute(p, self.compute_dtype),
                    out_shardings=self.param_shardings)(params)
                self.opt_state_shardings = ()
                opt_state = ()
            else:
                param_specs = jax.tree.map(
                    lambda s: s.spec, self.param_shardings,
                    is_leaf=lambda x: isinstance(x, NamedSharding))
                opt_state_shape = jax.eval_shape(self.tx.init, params)
                self.opt_state_shardings = build_opt_state_shardings(
                    opt_state_shape, params, param_specs, self.mesh,
                    max(self.zero_stage, 0), mics=self._mics)
                opt_state = jax.jit(self.tx.init,
                                    out_shardings=self.opt_state_shardings)(params)

            scalar_sharding = NamedSharding(self.mesh, PartitionSpec())
            self.state = EngineState(
                step=jax.device_put(jnp.int32(0), scalar_sharding),
                params=params,
                opt_state=opt_state,
                loss_scale=jax.device_put(precision.init_loss_scale(config.fp16),
                                          scalar_sharding),
                skipped_steps=jax.device_put(jnp.int32(0), scalar_sharding),
            )
            self.state_shardings = EngineState(
                step=scalar_sharding,
                params=self.param_shardings,
                opt_state=self.opt_state_shardings,
                loss_scale=jax.tree.map(lambda _: scalar_sharding,
                                        self.state.loss_scale),
                skipped_steps=scalar_sharding,
            )

        # hpZ secondary compute-copy shardings (stage 3 only; with the hpZ split
        # active, compute params are constrained to the inner fsdp sub-axis so
        # per-layer allgathers stay within the shard group)
        self._secondary_shardings = None
        if (self._hpz > 1 and self.zero_stage >= 3
                and self.mesh.shape.get("fsdp_out", 1) > 1):
            self._secondary_shardings = build_secondary_shardings(
                self.param_shardings, self.mesh)
        self._quantized_weights = bool(zc.zero_quantized_weights)
        if self._quantized_weights and self._secondary_shardings is None:
            log_dist("zero_quantized_weights (qwZ) takes effect on the hpZ "
                     "secondary gather; set zero_hpz_partition_size > 1 — ignored",
                     ranks=[0])
            self._quantized_weights = False
        # qgZ: quantized gradient reduction (reference all_to_all_quant_reduce,
        # runtime/comm/coalesced_collectives.py:31 + csrc/quantization/
        # quant_reduce.cu). When the mesh has replica batch axes (axes that
        # shard the batch but no parameter — the pure-DP all-reduce hops), the
        # gradient phase runs in a partial-manual shard_map and the reduction
        # over those axes moves REAL int8 bytes on the wire
        # (runtime/zero/qgz.py). Without replica axes (pure-fsdp ZeRO-3) the
        # reduction is fused into XLA's backward and the flag falls back to
        # the int8 round-trip numerics simulation in _grads_one_micro.
        self._quantized_gradients = bool(zc.zero_quantized_gradients)
        # replica (pure-DP) batch axes — shared by every wire-compression
        # feature that opens the partial-manual gradient phase (qgZ int8,
        # sparse embedding grads)
        from deepspeed_tpu.runtime.zero.qgz import replica_grad_axes
        self._replica_axes = replica_grad_axes(
            self.mesh, self.batch_spec, self.param_shardings) \
            if self._param_offload is None else ()
        self._qgz_axes = ()
        if self._quantized_gradients:
            self._qgz_axes = self._replica_axes
            if self._qgz_axes:
                log_dist("qgZ: int8-wire gradient reduction over replica "
                         f"axes {self._qgz_axes} (hierarchical quantized "
                         "reduce-scatter + regather)", ranks=[0])
            else:
                import warnings
                msg = ("zero_quantized_gradients=true but the mesh has NO "
                       "replica batch axis (pure-fsdp ZeRO-3): there is no "
                       "pure-DP all-reduce hop to compress, so NO bytes are "
                       "saved on the wire. Gradients still pay the int8 "
                       "round-trip quantization noise (reference-fidelity "
                       "numerics). Either add a replica axis (a 'data' mesh "
                       "axis, or split fsdp via mics_shard_size < world so "
                       "'fsdp_out' replicates) or disable "
                       "zero_quantized_gradients. See "
                       "docs/parallelism.md#qgz.")
                warnings.warn("qgZ: " + msg, UserWarning, stacklevel=3)
                logger.warning("qgZ: %s", msg)

        # --- resilience step guard -------------------------------------------
        # When armed, _update treats non-finite grads as an overflow in EVERY
        # precision mode (bf16/fp32 included): update dropped, params kept,
        # skipped_steps incremented. Armed from an explicit "resilience"
        # config group or at runtime via set_nonfinite_guard (the
        # FaultTolerantRunner's step-guard hook).
        rcfg = getattr(config, "resilience", None)
        self._guard_nonfinite = bool(
            getattr(config, "resilience_explicit", False) and rcfg is not None
            and rcfg.step_guard.enabled and rcfg.step_guard.policy == "skip")

        # --- compiled functions ----------------------------------------------
        self._reset_compiled_fns()

        # --- compat-shim bookkeeping ----------------------------------------
        self._grad_buffer = None
        self._accum_count = 0
        self._pending = None            # cached (loss, grads) from forward()

        # progressive layer drop (reference: engine.py:346 _configure_pld +
        # :1871 per-step update_state)
        self.progressive_layer_drop = None
        if config.pld.enabled:
            from deepspeed_tpu.runtime.progressive_layer_drop import (
                ProgressiveLayerDrop)
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=config.pld.theta, gamma=config.pld.gamma)
        # eigenvalue (reference: engine.py eigenvalue_enabled + compression MoQ)
        self.eigenvalue = None
        self.block_eigenvalues = None
        if config.eigenvalue.enabled:
            from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
            self.eigenvalue = Eigenvalue(config.eigenvalue)
        # sparse gradients (reference engine.py:2518 sparse_allreduce_bucket):
        # embedding-like leaves reduce over the replica axes with the sparse
        # (indices, values) wire format inside the partial-manual gradient
        # phase — same seam as qgZ; the two compose (sparse leaves go sparse,
        # the rest int8 when qgZ is also on)
        self.sparse_gradients_enabled = config.sparse_gradients_enabled
        self._sparse_grad_axes = ()
        self._sparse_grad_paths = ()
        if self.sparse_gradients_enabled:
            from deepspeed_tpu.utils.tree import tree_path_str
            # tied-embedding models get a DENSE head gradient over the whole
            # vocab — top-k truncation would silently drop real mass, so the
            # model's tie flag disables the path outright
            mcfg = getattr(model, "cfg", None)
            tied = bool(getattr(mcfg, "tie_embeddings", False) or
                        getattr(mcfg, "tie_word_embeddings", False))
            axes = self._replica_axes
            paths = () if tied else tuple(
                tree_path_str(p)
                for p, leaf in jax.tree_util.tree_flatten_with_path(
                    self.state.params)[0]
                if hasattr(leaf, "ndim") and leaf.ndim == 2
                and leaf.shape[0] >= 512
                and "embed" in tree_path_str(p).lower())
            if axes and paths:
                self._sparse_grad_axes = axes
                self._sparse_grad_paths = paths
                log_dist(
                    f"sparse_gradients: sparse wire reduction over {axes} "
                    f"for {len(paths)} embedding leaves (top-k rows = batch "
                    "tokens — exact for lookup-only embedding grads)",
                    ranks=[0])
            else:
                log_dist(
                    "sparse_gradients: "
                    + ("model ties its embeddings (dense head grads) — "
                       if tied else
                       "no replica batch axis or no embedding-like leaf — ")
                    + "gradients reduce densely", ranks=[0])

        # --- comm compression (comm/compress.py) ------------------------------
        # Quantized error-feedback collectives + bucketed backward/
        # reduce-scatter overlap over the replica axes. Default OFF =
        # today's exact semantics. When active it OWNS the wire: qgZ
        # (zero_quantized_gradients) defers to it — one compression layer,
        # one error-feedback state, one set of wire-byte counters.
        ccfg = config.comm_compression
        self._comm_compress = None
        self._overlap_meta: List[Dict[str, Any]] = []
        self._overlap_wire_total = 0
        if ccfg.enabled:
            if self._param_offload is not None or self._offload is not None:
                log_dist("comm_compression: disabled — offload tiers run a "
                         "host-synchronous optimizer step whose reductions "
                         "keep today's wire format", ranks=[0])
            elif not self._replica_axes:
                import warnings
                msg = ("comm_compression enabled but the mesh has NO "
                       "replica batch axis (pure-fsdp ZeRO-3): there is no "
                       "pure-DP all-reduce hop to compress, so NO bytes "
                       "are saved on the wire — the group is ignored. Add "
                       "a replica axis (a 'data' mesh axis, or split fsdp "
                       "via mics_shard_size < world so 'fsdp_out' "
                       "replicates). See docs/performance.md#wire-"
                       "compression--overlap.")
                warnings.warn(msg, UserWarning, stacklevel=3)
                logger.warning(msg)
            else:
                from deepspeed_tpu.comm.compress import (CommCompressState,
                                                         GradCompressor,
                                                         with_error_feedback)
                comp = GradCompressor(ccfg, self._replica_axes, self.mesh)
                comp.build(self.state.params,
                           itemsize=jnp.dtype(config.grad_accum_dtype)
                           .itemsize,
                           exclude_paths=self._sparse_grad_paths)
                if not comp.buckets:
                    log_dist("comm_compression: no leaf meets min_size "
                             f"({ccfg.min_size}) — nothing to compress",
                             ranks=[0])
                else:
                    self._comm_compress = comp
                    # overlap spans describe the per-bucket schedule; a
                    # fused single bucket (overlap=False) has no schedule
                    # to claim, so nothing rides the comm-overlap track
                    self._overlap_meta = comp.bucket_summaries() \
                        if ccfg.overlap else []
                    self._overlap_wire_total = max(
                        sum(b["wire_bytes"] for b in self._overlap_meta), 1)
                    if self._quantized_gradients:
                        log_dist("comm_compression supersedes "
                                 "zero_quantized_gradients on the replica "
                                 "axes (one compression layer owns the "
                                 "wire)", ranks=[0])
                        self._qgz_axes = ()
                        # clearing the axes alone would re-arm the
                        # per-microbatch int8 round-trip fallback in
                        # _grads_one_micro — the wire is quantized ONCE,
                        # by the bucketed reduction
                        self._quantized_gradients = False
                    # error-feedback residuals ride the optimizer state so
                    # they checkpoint and survive the mesh-portable resume
                    ef_shardings = comp.error_feedback_shardings(self.mesh)
                    ef = jax.jit(comp.zero_error_feedback,
                                 out_shardings=ef_shardings)() \
                        if comp.ef_enabled() else ()
                    self.tx = with_error_feedback(self.tx,
                                                  comp.zero_error_feedback)
                    self.state = self.state._replace(
                        opt_state=CommCompressState(
                            inner=self.state.opt_state, error_feedback=ef))
                    self.opt_state_shardings = CommCompressState(
                        inner=self.opt_state_shardings,
                        error_feedback=ef_shardings)
                    self.state_shardings = self.state_shardings._replace(
                        opt_state=self.opt_state_shardings)
                    log_dist(
                        f"comm_compression: {len(comp.buckets)} bucket(s) "
                        f"over {self._replica_axes} "
                        f"(wire={ccfg.wire_dtype}, chunk={ccfg.chunk}, "
                        f"error_feedback={'on' if comp.ef_enabled() else 'off'}, "
                        f"overlap={'per-bucket' if ccfg.overlap else 'fused'})",
                        ranks=[0])

        # --- async step pipeline (deferred metric readback + prefetch) --------
        # config.async_pipeline; disabled -> per-step readback semantics are
        # bit-for-bit today's (no ring, no extra sync, device-array metrics)
        acfg = config.async_pipeline
        self._async_enabled = bool(acfg.enabled)
        if self._async_enabled and (self._param_offload is not None
                                    or self._offload is not None):
            # the fused host-optimizer step is host-synchronous by
            # construction — a deferred ring would never fill and async-mode
            # consumers (the resilience runner) would go blind
            log_dist("async_pipeline: disabled — offload tiers run a "
                     "host-synchronous optimizer step (nothing to defer)",
                     ranks=[0])
            self._async_enabled = False
        # the configured cadence survives enable/disable toggles; the live
        # _sync_every collapses to 1 whenever the pipeline is off
        self._sync_every_cfg = int(acfg.sync_every)
        # the shared host-orchestration core (runtime/sched.py): DispatchRing
        # owns the device-side pending ring, the bounded drained-entry queue
        # and the window anchor; StagedPrefetcher owns the identity-keyed
        # loader lifecycle. The serve loop consumes the same classes —
        # engine-specific host fan-out stays in _drain_metric_ring.
        self._sched = DispatchRing(capacity=4096)
        self._staged = StagedPrefetcher()
        self._sync_every = self._sync_every_cfg if self._async_enabled else 1
        self._prefetch_enabled = self._async_enabled and bool(acfg.prefetch)
        if self._prefetch_enabled and (config.flops_profiler.enabled
                                       or config.eigenvalue.enabled):
            # both side paths materialize the batch on host (np.asarray),
            # which a staged multi-host array cannot satisfy — profiling /
            # diagnostic runs keep inline staging
            log_dist("async_pipeline: prefetch disabled — flops_profiler/"
                     "eigenvalue need host-materialized batches", ranks=[0])
            self._prefetch_enabled = False
        self._prefetch_depth = int(acfg.prefetch_depth)
        if self._async_enabled and config.wall_clock_breakdown:
            log_dist("async_pipeline: wall_clock_breakdown forces a device "
                     "sync per timer start/stop — the breakdown timers will "
                     "serialize the pipeline they are measuring", ranks=[0])

        # --- bookkeeping / observability -------------------------------------
        self.tracer = get_tracer()     # dstrace span tracer (DSTPU_TRACE)
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.timers = SynchronizedWallClockTimer(
            synchronize=config.wall_clock_breakdown)
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size,
            steps_per_output=config.steps_per_print,
            synchronize=not self._async_enabled)
        self._last_metrics: Dict[str, float] = {}
        self.monitor = None
        if (config.tensorboard.enabled or config.csv_monitor.enabled
                or config.wandb.enabled):
            from deepspeed_tpu.monitor.monitor import MonitorMaster
            self.monitor = MonitorMaster(config)
            if self.monitor.enabled:
                # tracer instant-events (guard trips, chaos injections, ...)
                # fan out to the monitor's `events` sink alongside gauges.
                # Bound through a weakref: the process-global tracer outlives
                # any engine, and a strong bound method would pin a torn-down
                # engine's monitor (open TB/CSV handles) for the process
                # lifetime and keep routing events to its stale writers.
                mon_ref = weakref.ref(self.monitor)

                def _events_sink(name, step):
                    mon = mon_ref()
                    if mon is not None:
                        mon.write_instant(name, step)

                self.tracer.attach_sink(_events_sink)

        # --- data efficiency (curriculum learning + random-LTD) --------------
        # reference: engine.py curriculum hooks + runtime/data_pipeline/
        self.curriculum_scheduler = None
        self.random_ltd_scheduler = None
        if config.curriculum_learning_legacy.enabled:
            from deepspeed_tpu.data_pipeline import CurriculumScheduler
            c = config.curriculum_learning_legacy
            self.curriculum_scheduler = CurriculumScheduler({
                "schedule_type": c.schedule_type,
                "min_difficulty": c.min_difficulty,
                "max_difficulty": c.max_difficulty,
                "schedule_config": c.schedule_config})
        # per-metric curriculum sampling lives in CurriculumDataSampler (which owns
        # its schedulers); the engine only drives the legacy seqlen curriculum + LTD
        if config.data_efficiency.random_ltd_enabled:
            from deepspeed_tpu.data_pipeline import RandomLTDScheduler
            ltd = dict(config.data_efficiency.random_ltd)
            ltd.setdefault("global_batch_size", self.train_batch_size)
            self.random_ltd_scheduler = RandomLTDScheduler(ltd)

        # --- compression (QAT / pruning; reference deepspeed/compression) -----
        self.compressor = None
        self._compression_key = None
        if config.compression_config:
            from deepspeed_tpu.compression import init_compression
            self.compressor = init_compression(
                self.state.params,
                {"compression_training": config.compression_config})
            self.compressor.maybe_freeze_masks(self.state.params)
            self._compression_key = self.compressor.schedule_key()

        # --- dsmem: memory observability + analytic preflight ------------------
        # the sampler rides every traced run for free (HBM/RSS counter
        # tracks in the DSTPU_TRACE dump); the "memory" config group adds
        # the analytic preflight and the background cadence thread
        self._mem_sampler = None
        self.last_oom: Optional[Dict[str, Any]] = None
        if config.memory.enabled or self.tracer.enabled:
            from deepspeed_tpu.telemetry.memory import MemorySampler
            self._mem_sampler = MemorySampler(tracer=self.tracer,
                                              window=config.memory.window)
            if config.memory.enabled and config.memory.cadence_s > 0:
                self._mem_sampler.start(config.memory.cadence_s)
        if config.memory.enabled and config.memory.preflight != "off":
            self._memory_preflight(config.memory.preflight)
        if self._mem_sampler is not None:
            # the init watermark: params + optimizer state are resident now
            self._mem_sampler.sample(step=0, phase="init")

    @staticmethod
    def _host_init_params(model, example_batch, init_rng):
        """Initialize params in HOST memory (CPU backend): under offload_param
        the model may not fit device HBM, so device-side init is not an
        option. Falls back to default-device init + fetch when no CPU backend
        exists (then the model must fit HBM once; pass ``params`` to avoid)."""
        if not hasattr(model, "init"):
            raise ValueError("pass `params` or a flax Module with .init")

        def _init(r):
            return model.init(r, example_batch)["params"]
        try:
            cpu = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            log_dist("offload_param: no CPU backend for host init — "
                     "initializing on the default device (model must fit HBM "
                     "once; pass `params` for weights-bigger-than-HBM runs)",
                     ranks=[0])
            return jax.device_get(jax.jit(_init)(init_rng))
        with jax.default_device(cpu):
            return jax.device_get(jax.jit(_init)(jax.device_put(init_rng, cpu)))

    def _reset_compiled_fns(self):
        """Drop every cached compiled step fn. The single authority for the set of
        jitted-fn caches — used at init and whenever static trace structure
        changes (e.g. a compression-schedule transition)."""
        if not hasattr(self, "training"):
            # API-parity mode flags are set once: a cache reset (compression
            # transition, checkpoint load) must not undo a user's eval() /
            # compile() calls (round-2 advisor finding).
            self.training = True        # module-mode parity (train()/eval())
            self._compiled = False      # engine.compile() parity flag
        self._train_batch_fn = None     # gas microbatches fused via scan
        self._micro_fwd_bwd_fn = None   # compat path: per-microbatch grads
        self._apply_update_fn = None    # compat path: update at boundary
        self._eval_fn = None
        self._offload_grad_fn = None
        self._offload_apply_fn = None

    # ------------------------------------------------------------------
    # loss computation
    # ------------------------------------------------------------------
    def _hpz_constrain(self, compute_params):
        """ZeRO++ hpZ: re-lay the compute copy onto the secondary (inner-group)
        sharding — one cross-group gather here, node-local gathers per layer.
        With qwZ the cross-group hop moves int8 + per-row scales instead of the
        compute dtype (reference: quantized-weights allgather, CUDAQuantizer
        partition_parameters.py:761)."""
        if not self._quantized_weights:
            return jax.lax.with_sharding_constraint(
                compute_params, self._secondary_shardings)

        def requantize(leaf, primary, sharding):
            # only quantize leaves whose layout actually changes across the
            # fsdp_out hop — replicated / tensor-only leaves have no cross-group
            # gather to cheapen, so int8 noise there is pure loss
            if (leaf.ndim < 2 or not jnp.issubdtype(leaf.dtype, jnp.floating)
                    or primary.spec == sharding.spec):
                return jax.lax.with_sharding_constraint(leaf, sharding)
            s_spec = PartitionSpec(*(list(sharding.spec)[:leaf.ndim - 1] + [None])) \
                if len(sharding.spec) else PartitionSpec()
            return _qwz_regather(leaf, sharding,
                                 NamedSharding(self.mesh, s_spec))

        return jax.tree.map(requantize, compute_params, self.param_shardings,
                            self._secondary_shardings)

    def _compute_loss(self, params, batch, rng):
        compute_params = precision.cast_to_compute(params, self.compute_dtype)
        if self._secondary_shardings is not None:
            compute_params = self._hpz_constrain(compute_params)
        if self.compressor is not None:
            # fake-quant + pruning masks with straight-through grads, traced into
            # the step under the current host-side schedule snapshot
            compute_params = self.compressor.transform(compute_params)
        out = self._apply_fn(compute_params, batch, rng)
        if self.loss_fn is not None:
            out = self.loss_fn(out, batch)
        if isinstance(out, tuple):
            out = out[0]
        return jnp.asarray(out, jnp.float32)

    def _grads_one_micro(self, params, batch, rng, scale):
        """Value-and-grad of (scaled) loss for one microbatch. With qgZ on and
        no replica axis to carry the real int8-wire collective, every
        microbatch gradient goes through an int8 round-trip before it is
        accumulated/reduced — the fidelity contract of the reference's
        quantized-gradient collectives. With replica axes present the wire
        quantization itself supplies the numerics (runtime/zero/qgz.py)."""
        def scaled_loss(p):
            return self._compute_loss(p, batch, rng) * scale
        loss_scaled, grads = jax.value_and_grad(scaled_loss)(params)
        if self._quantized_gradients and not self._qgz_axes:
            from deepspeed_tpu.ops.pallas.quant import dequantize_int8, quantize_int8
            from deepspeed_tpu.runtime.zero.qgz import MIN_QUANT_SIZE

            def qdq(g):
                # tiny leaves (norm scales, biases) are bandwidth-irrelevant —
                # the reference buckets them with everything else, but skipping
                # them avoids int8 noise on the most sensitive parameters
                # (same threshold as the wire path, qgz.MIN_QUANT_SIZE)
                if g.ndim < 1 or g.size < MIN_QUANT_SIZE:
                    return g
                q, s = quantize_int8(g)
                return dequantize_int8(q, s, dtype=g.dtype)
            grads = jax.tree.map(qdq, grads)
        return loss_scaled / scale, grads

    # ------------------------------------------------------------------
    # fused train_batch: scan over gas microbatches + update, one jit
    # ------------------------------------------------------------------
    def _make_grads_phase(self):
        """Builds ``(params, stacked_batch [gas, ...], rngs [gas], scale) ->
        (avg loss, per-micro-summed grads in grad_accum_dtype)``. When qgZ has
        replica axes, the whole phase (fwd/bwd + gas scan) runs inside a
        partial-manual shard_map: per-device partial grads, then an int8-wire
        hierarchical reduce over the replica axes — real bandwidth compression,
        not just the reference's numerics (runtime/zero/qgz.py). fsdp/tensor
        axes stay XLA-automatic inside the region."""
        gas = self.gradient_accumulation_steps
        acc_dtype = self.config.grad_accum_dtype

        def grads_phase(params, stacked_batch, rngs, scale):
            if gas == 1:
                # no accumulation buffer at all: one microbatch, grads go
                # straight into the update (saves a full param-tree carry)
                batch = jax.tree.map(lambda x: x[0], stacked_batch)
                loss, grads = self._grads_one_micro(params, batch,
                                                    rngs[0], scale)
                return loss, jax.tree.map(lambda g: g.astype(acc_dtype), grads)

            def micro(carry, xs):
                grad_acc, loss_acc = carry
                batch, r = xs
                loss, grads = self._grads_one_micro(params, batch, r, scale)
                grad_acc = jax.tree.map(
                    lambda a, g: a + g.astype(acc_dtype), grad_acc, grads)
                return (grad_acc, loss_acc + loss), None

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params)
            (grads, loss_sum), _ = jax.lax.scan(
                micro, (zero_grads, jnp.float32(0.0)), (stacked_batch, rngs))
            return loss_sum / gas, grads

        from deepspeed_tpu.runtime.zero.qgz import wrap_grads_phase
        if self._comm_compress is not None:
            # comm_compression owns the manual-region reduction: per-bucket
            # facade-recorded quantized all-reduce with the error-feedback
            # residuals threaded through the shard_map (sparse embedding
            # leaves keep their sparse wire format via the fallback)
            comp = self._comm_compress
            axes = self._replica_axes
            sync = comp.make_sync_fn(
                fallback_leaf_sync=self._compress_fallback_sync(axes))
            if comp.ef_enabled():
                return wrap_grads_phase(grads_phase, self.mesh, axes,
                                        self.batch_spec, stacked=True,
                                        sync_fn=sync,
                                        ef_specs=comp.ef_partition_specs())

            def sync_no_ef(grads, batch):
                reduced, _ = sync(grads, batch, ())
                return reduced

            return wrap_grads_phase(grads_phase, self.mesh, axes,
                                    self.batch_spec, stacked=True,
                                    sync_fn=sync_no_ef)
        axes = self._qgz_axes or self._sparse_grad_axes
        return wrap_grads_phase(grads_phase, self.mesh, axes,
                                self.batch_spec, stacked=True,
                                sync_fn=self._make_grad_sync(axes))

    @staticmethod
    def _batch_token_count(batch) -> int:
        """k = batch tokens on this device: a pure-lookup embedding grad
        touches at most one row per token, so top-k at this k keeps every
        touched row and the sparse reduction is EXACT. Max over integer
        leaves — small int side fields (bucket ids, lengths) must not
        shrink k below the token count."""
        return max((int(leaf.size) for leaf in jax.tree.leaves(batch)
                    if jnp.issubdtype(leaf.dtype, jnp.integer)),
                   default=0)

    def _sparse_wire_policy(self, axes):
        """THE sparse-embedding wire rule, shared by the composite grad
        sync and the comm_compression fallback so the win heuristic can
        never drift between them: returns ``fn(path_str, g, k_tokens) ->
        reduced | None`` (None = not a sparse-profitable leaf — caller
        falls through to its dense policy), or None when no sparse leaves
        are configured."""
        if not self._sparse_grad_paths or not axes:
            return None
        from deepspeed_tpu.runtime.sparse_tensor import sparse_grad_sync
        sparse_paths = set(self._sparse_grad_paths)
        world = 1
        for ax in axes:
            world *= self.mesh.shape[ax]

        def leaf_rule(p, g, k_tokens):
            if p not in sparse_paths or not k_tokens:
                return None
            v, d = g.shape
            k = min(v, k_tokens)
            # wire win vs dense: the gathered sparse representation is
            # O(k·(d+1)·world) rows across the replica group, a dense
            # all-reduce O(v·d) — sparse only pays when the batch's token
            # set is small relative to V/world
            if k * (d + 1) * world < v * d:
                return sparse_grad_sync(g, axes, k)
            return None

        return leaf_rule

    def _compress_fallback_sync(self, axes):
        """Leaf sync for leaves OUTSIDE every compression bucket
        (sub-min_size, non-float, or sparse-selected): sparse embedding
        leaves keep the sparse (indices, values) wire format, everything
        else a full-precision pmean. None when no sparse leaves are
        configured (the compressor's default pmean fallback applies)."""
        sparse_rule = self._sparse_wire_policy(axes)
        if sparse_rule is None:
            return None
        from deepspeed_tpu.utils.tree import tree_path_str

        def fallback(path, g, batch):
            out = sparse_rule(tree_path_str(path), g,
                              self._batch_token_count(batch))
            return jax.lax.pmean(g, axes) if out is None else out

        return fallback

    def _make_grad_sync(self, axes):
        """Per-leaf wire policy for the manual-region gradient reduction:
        embedding leaves (sparse_gradients) use the sparse (indices, values)
        format via the shared ``_sparse_wire_policy`` rule, everything else
        int8 (qgZ) or plain fp pmean. Returns None (the default quantized
        sync) when no sparse leaves are selected."""
        sparse_rule = self._sparse_wire_policy(axes)
        if sparse_rule is None:
            return None
        from deepspeed_tpu.runtime.zero.qgz import quantized_grad_sync
        from deepspeed_tpu.utils.tree import tree_path_str
        qgz_on = bool(self._qgz_axes)

        def sync_fn(grads, batch):
            k_tokens = self._batch_token_count(batch)

            def leaf_sync(path, g):
                out = sparse_rule(tree_path_str(path), g, k_tokens)
                if out is not None:
                    return out
                if qgz_on:
                    return quantized_grad_sync(g, axes)
                return jax.lax.pmean(g, axes)

            return jax.tree_util.tree_map_with_path(leaf_sync, grads)

        return sync_fn

    def _build_train_batch_fn(self):
        cfg = self.config
        gas = self.gradient_accumulation_steps
        clip = cfg.gradient_clipping
        fp16 = cfg.fp16
        tx = self.tx
        lr_schedule = self.lr_schedule
        grads_phase = self._make_grads_phase()

        ef_active = (self._comm_compress is not None
                     and self._comm_compress.ef_enabled())

        def train_batch_step(state: EngineState, stacked_batch, rng) -> Tuple[EngineState, StepOutput]:
            scale = state.loss_scale.scale
            rngs = jax.random.split(rng, gas)
            if ef_active:
                # comm_compression error feedback: residuals ride the
                # optimizer-state wrapper into the manual region and come
                # back refreshed by the bucketed quantized reduction
                ef = state.opt_state.error_feedback
                loss, grads, new_ef = grads_phase(state.params,
                                                  stacked_batch, rngs,
                                                  scale, ef)
            else:
                loss, grads = grads_phase(state.params, stacked_batch,
                                          rngs, scale)
            # unscale + average over gas in fp32 (reference scales loss by 1/gas
            # pre-bwd; accumulation dtype may be lower via data_types config).
            # No per-microbatch overflow check is needed (the reference checks
            # per-reduction, stage3.py:1290): IEEE non-finites are absorbing
            # under addition (inf + -inf = NaN, inf + x = inf), so any
            # microbatch overflow survives into the accumulated sum and the
            # single check in _update catches it — tested in
            # test_fp16_per_microbatch_overflow_detected.
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / (scale * gas), grads)
            new_state, out = self._update(state, grads, tx, lr_schedule, clip, fp16)
            if ef_active:
                # a residual refreshed from non-finite grads would poison
                # every later step: on overflow the old residuals survive
                # with the params (exactly the keep_old contract)
                kept = jax.tree.map(
                    lambda n, o: jnp.where(out.overflow, o, n), new_ef, ef)
                new_state = new_state._replace(
                    opt_state=new_state.opt_state._replace(
                        error_feedback=kept))
            return new_state, out._replace(loss=loss)

        donate = (0,)
        # watch_jit: every XLA compile of the step fn emits an xla/compile
        # instant (qualname + shape signature + wall ms) and bumps the
        # process compile counter — benches assert ZERO compiles inside
        # their timed window after warmup (telemetry/compiles.py)
        self._train_batch_fn = watch_jit(jax.jit(
            train_batch_step,
            donate_argnums=donate,
            out_shardings=(self.state_shardings, None),
        ), "engine.train_batch_step")

    @jax.named_scope("optimizer")      # the update's name in a device trace
    def _update(self, state: EngineState, grads, tx, lr_schedule, clip,
                fp16) -> Tuple[EngineState, StepOutput]:
        """Optimizer update with overflow skip + dynamic loss scale + clipping.
        reference: stage3.py step (:2061) / fused_optimizer.py step."""
        if fp16.enabled or self._guard_nonfinite:
            # fp16: detect overflow, neutralize non-finite grads so the (discarded)
            # update arithmetic stays clean, and skip the step (reference
            # _overflow_check_and_loss_scale_update). This single post-sum
            # check also covers per-microbatch overflow under the gas scan —
            # IEEE non-finites are absorbing under addition. The resilience
            # step guard reuses the same path for bf16/fp32 (skip, no scaler).
            overflow = precision.has_inf_or_nan(grads)
            safe_grads = jax.tree.map(
                lambda g: jnp.where(jnp.isfinite(g), g, jnp.zeros_like(g)), grads)
        else:
            # bf16/fp32 without the guard: no loss scaler in the reference
            # either — a NaN propagates into params/loss so divergence is
            # visible, never silently masked.
            overflow = jnp.bool_(False)
            safe_grads = grads
        clipped, grad_norm = precision.clip_by_global_norm(safe_grads, clip)
        updates, new_opt_state = tx.update(clipped, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)

        def keep_old(new, old):
            return jax.tree.map(lambda n, o: jnp.where(overflow, o, n), new, old)

        new_params = keep_old(new_params, state.params)
        new_opt_state = keep_old(new_opt_state, state.opt_state)
        new_scale_state = precision.update_loss_scale(state.loss_scale, overflow, fp16)
        lr = jnp.asarray(lr_schedule(state.step), jnp.float32)
        new_state = EngineState(
            step=state.step + jnp.where(overflow, 0, 1).astype(jnp.int32),
            params=new_params,
            opt_state=new_opt_state,
            loss_scale=new_scale_state,
            skipped_steps=state.skipped_steps + overflow.astype(jnp.int32),
        )
        return new_state, StepOutput(loss=jnp.float32(0.0), grad_norm=grad_norm,
                                     lr=lr, overflow=overflow)

    @staticmethod
    def stack_microbatches(data_iter, gas: int):
        """Pull ``gas`` microbatches and stack every leaf to [gas, ...] —
        THE stacked-batch contract train_batch consumes (shared with the
        resilience runner so the two never drift)."""
        micro = [next(data_iter) for _ in range(gas)]
        return jax.tree.map(lambda *xs: np.stack(xs), *micro)

    def _shard_batch(self, batch, stacked: bool):
        """Place a host batch on the mesh: [B, ...] (or [gas, B, ...]) with B split
        over the DP axes. Multi-host: each process supplies its local shard of the
        global batch (reference: distributed sampler), assembled with
        make_array_from_process_local_data."""
        multi_host = jax.process_count() > 1

        def place(x):
            x = np.asarray(x)
            spec = self.batch_spec
            if stacked:
                spec = PartitionSpec(None, *spec)
            sharding = NamedSharding(self.mesh, spec)
            if multi_host:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)
        tr = self.tracer
        nbytes = sum(int(getattr(x, "nbytes", 0))
                     for x in jax.tree.leaves(batch)) if tr.enabled else 0
        with tr.span("comm/h2d", cat="comm", bytes=nbytes):
            return jax.tree.map(place, batch)

    def train_batch(self, data_iter: Optional[Iterator] = None,
                    batch: Optional[Any] = None, stacked: Optional[bool] = None) -> jnp.ndarray:
        """Run one full training batch (gas microbatches + optimizer update) as one
        compiled step. Pass either an iterator yielding microbatches (reference
        ``PipelineEngine.train_batch`` contract) or ``batch`` whose leaves are
        stacked [gas, micro_global, ...]. When gas == 1 an unstacked
        [micro_global, ...] batch is accepted (``stacked=True`` overrides)."""
        gas = self.gradient_accumulation_steps
        fused_path = self._param_offload is None and self._offload is None
        if batch is None:
            if data_iter is None:
                raise ValueError("train_batch needs data_iter or batch")
            if self._prefetch_enabled and fused_path:
                # background double buffer: stack + device_put happen one
                # step ahead, so batch N+1's H2D overlaps batch N's compute
                batch = next(self._ensure_prefetcher(data_iter))
            else:
                batch = self.stack_microbatches(data_iter, gas)
        elif gas == 1 and not stacked and not isinstance(batch, StagedBatch):
            # deterministic rule (no shape-guessing): gas==1 batches are unstacked
            # unless the caller says otherwise
            batch = jax.tree.map(lambda x: np.asarray(x)[None], batch)
        # rare host-side consumers (profiler/eigenvalue) read through the wrapper
        host_view = batch.arrays if isinstance(batch, StagedBatch) else batch
        if (self.config.flops_profiler.enabled
                and self.global_steps == self.config.flops_profiler.profile_step):
            self._run_flops_profile(host_view)
        if self._param_offload is not None:
            return self._train_batch_param_offload(host_view)
        if self._offload is not None:
            return self._train_batch_offloaded(host_view)
        if self._train_batch_fn is None:
            self._build_train_batch_fn()
        if isinstance(batch, StagedBatch):
            device_batch = batch.arrays    # prefetch thread already staged it
        else:
            device_batch = self._shard_batch(batch, stacked=True)
        self._rng, step_rng = jax.random.split(self._rng)

        # async mode times *dispatch* per step (no completion wait); the true
        # step time is reconciled into TRAIN_BATCH_TIMER at each ring drain
        step_timer = self.timers(TRAIN_BATCH_DISPATCH_TIMER
                                 if self._async_enabled else TRAIN_BATCH_TIMER)
        if self._async_enabled and not self._metric_ring:
            # empty ring = a fresh window: anchor it at this dispatch, so
            # host pauses between windows (checkpoint I/O, idle gaps after a
            # flush) are never booked as step time at the next drain
            self._last_drain_time = time.time()
        self.tput_timer.start()
        step_timer.start()
        # dispatch span: host time spent LAUNCHING the fused step (no
        # completion wait — in async mode the reconciled step time shows up
        # as engine/steps_reconciled at the drain; comparing the two is the
        # dispatch-gap-vs-step-time view the async pipeline is tuned by)
        if self._mem_sampler is not None:
            # phase transition is attribute stores (hot-path safe): the
            # first dispatched step carries compile workspace the analytic
            # plan does not model, so it gets its own observation bucket.
            # In async mode the first SAMPLE happens at the first drain
            # (up to sync_every steps later) — hold "first_step" until one
            # sample lands in it, else the bucket would be overwritten to
            # "steady" before it was ever observed; the 2x-sync_every step
            # guard bounds the hold for cadence-thread-only configs
            sampler = self._mem_sampler
            if self.global_steps == 0:
                sampler.phase = "first_step"
            elif sampler.phase == "first_step" and (
                    sampler.seen("first_step")
                    or self.global_steps >= 2 * max(self._sync_every or 1,
                                                    1)):
                sampler.phase = "steady"
        overlap_trace = (self._comm_compress is not None
                         and self.tracer.enabled)
        t_dispatch0 = time.monotonic() if overlap_trace else 0.0
        try:
            with self.tracer.span(
                    "engine/dispatch", cat="train", step=self.global_steps,
                    mode="async" if self._async_enabled else "sync"):
                self.state, out = self._train_batch_fn(self.state,
                                                       device_batch,
                                                       step_rng)
        except Exception as e:
            # compile-time RESOURCE_EXHAUSTED raises at dispatch: classify
            # and stash forensics before the error unwinds (no-op otherwise)
            self._note_oom(e)
            raise
        if overlap_trace:
            self._emit_overlap_spans(t_dispatch0, time.monotonic())
        step_timer.stop()
        self.tput_timer.stop(global_step=True)

        self.global_steps += 1
        self.micro_steps += gas
        self.global_samples += self.train_batch_size
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        if self.eigenvalue is not None and self.global_steps % max(
                self.eigenvalue.cfg.gas_boundary_resolution, 1) == 0:
            # reference: eigenvalue at gas boundaries feeding compression MoQ
            # (engine.py quantizer hooks); results cached on the engine
            eval_batch = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)[0]),
                                      host_view)
            self.block_eigenvalues = self.eigenvalue.compute_eigenvalue(
                lambda p: self._compute_loss(p, eval_batch,
                                             jax.random.PRNGKey(0)),
                self.state.params, jax.random.PRNGKey(self.global_steps))
        self._advance_data_schedules()
        self._record_metrics(out)
        return out.loss

    def _train_batch_param_offload(self, batch) -> jnp.ndarray:
        """ZeRO-Infinity parameter-offload step: the streamed layer-group
        fwd/bwd + fused host optimizer in runtime/param_offload.py."""
        batch_host = {k: np.asarray(v) for k, v in batch.items()}
        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        applied_step = self.global_steps   # the step the offload optimizer
        with self.tracer.span("engine/train_step", cat="train",
                              step=applied_step, mode="param_offload"):
            loss, norm = self._param_offload.train_batch(  # evaluates lr at
                batch_host, step=applied_step)
        self.timers(TRAIN_BATCH_TIMER).stop()
        self.tput_timer.stop(global_step=True)
        self.state = self.state._replace(step=self.state.step + 1)
        self.global_steps += 1
        self.micro_steps += self.gradient_accumulation_steps
        self.global_samples += self.train_batch_size
        self._advance_data_schedules()
        # report the lr that was ACTUALLY applied (pre-increment step), not
        # the next step's schedule value
        lr = float(jax.device_get(self.lr_schedule(jnp.int32(applied_step))))
        self._record_metrics(StepOutput(
            loss=jnp.float32(loss), grad_norm=jnp.float32(norm),
            lr=jnp.float32(lr), overflow=jnp.bool_(False)), sync=True)
        # stream observability: H2D volume + phase split (monitor fan-out
        # picks these up alongside the standard Train/Samples events)
        self._last_metrics["param_offload_bytes_streamed"] = float(
            self._param_offload.bytes_streamed)
        for phase, secs in self._param_offload.phase_seconds.items():
            self._last_metrics[f"param_offload_{phase}_s"] = secs
        return jnp.float32(loss)

    def _train_batch_offloaded(self, batch) -> jnp.ndarray:
        """ZeRO-Offload step: device grads under jit, fused C++ host optimizer on
        fp32 masters, compute-dtype shadow back to device (reference: CPU
        optimizer step stage3.py:964 with offload). The device<->host round trip
        is the cost the reference pays too; overlap comes from the async swapper
        inside. fp16 loss scaling + overflow step-skip match the in-HBM path."""
        cfg = self.config
        if self._offload_grad_fn is None:
            gas = self.gradient_accumulation_steps
            fp16 = cfg.fp16

            grads_phase = self._make_grads_phase()

            def grad_step(params, stacked_batch, rng, scale):
                rngs = jax.random.split(rng, gas)
                loss, grads = grads_phase(params, stacked_batch, rngs, scale)
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) / (scale * gas), grads)
                overflow = precision.has_inf_or_nan(grads) \
                    if (fp16.enabled or self._guard_nonfinite) \
                    else jnp.bool_(False)
                if cfg.gradient_clipping > 0:
                    grads, norm = precision.clip_by_global_norm(
                        grads, cfg.gradient_clipping)
                else:
                    norm = precision.global_grad_norm(grads)
                return loss, grads, norm, overflow

            self._offload_grad_fn = watch_jit(jax.jit(grad_step),
                                              "engine.offload_grad_step")

        device_batch = self._shard_batch(batch, stacked=True)
        self._rng, r = jax.random.split(self._rng)
        self.tput_timer.start()
        with self.tracer.span("engine/train_step", cat="train",
                              step=self.global_steps, mode="offload"):
            loss, grads, norm, overflow = self._offload_grad_fn(
                self.state.params, device_batch, r,
                self.state.loss_scale.scale)
            self._offload_host_update(loss, grads, norm, overflow)
        self.tput_timer.stop(global_step=True)
        self.global_steps += 1
        self.micro_steps += self.gradient_accumulation_steps
        self.global_samples += self.train_batch_size
        self._advance_data_schedules()
        return loss

    def _offload_host_update(self, loss, grads, norm, overflow):
        """Host half of an offloaded step: on overflow skip the update and shrink
        the loss scale (parity with _update's keep_old/skip); otherwise run the
        fused CPU kernel on the masters and push a compute-dtype shadow back."""
        cfg = self.config
        overflow_host = bool(jax.device_get(overflow))
        lr = float(jax.device_get(self.lr_schedule(self.state.step)))
        new_scale = precision.update_loss_scale(
            self.state.loss_scale, overflow, cfg.fp16) if cfg.fp16.enabled \
            else self.state.loss_scale
        if overflow_host:
            self.state = self.state._replace(
                loss_scale=new_scale,
                skipped_steps=self.state.skipped_steps + 1)
        else:
            grads_host = [np.asarray(jax.device_get(g))
                          for g in jax.tree.leaves(grads)]
            self._offload.step(grads_host, lr=lr)
            shadow = self._offload.shadows(np.dtype(self.compute_dtype).name)
            new_params = jax.tree_util.tree_unflatten(self._params_treedef, shadow)
            self.state = self.state._replace(
                params=jax.device_put(new_params, self.param_shardings),
                step=self.state.step + 1,
                loss_scale=new_scale)
        self._record_metrics(StepOutput(loss=loss, grad_norm=norm,
                                        lr=jnp.float32(lr), overflow=overflow),
                             sync=True)

    def set_nonfinite_guard(self, enabled: bool = True) -> None:
        """Arm/disarm the resilience step guard: with it armed, non-finite
        grads are treated exactly like an fp16 overflow in every precision
        mode — the update is dropped, params stay at the last good step, and
        ``skipped_steps`` increments (reference: CheckOverflow generalized
        past the loss scaler). Toggling re-traces the compiled step."""
        enabled = bool(enabled)
        if self._guard_nonfinite != enabled:
            self._guard_nonfinite = enabled
            self._reset_compiled_fns()
            log_dist(f"non-finite step guard {'armed' if enabled else 'off'}",
                     ranks=[0])

    def _emit_overlap_spans(self, t0: float, t1: float) -> None:
        """Per-bucket ``comm/overlap`` retro-spans on the dedicated
        synthetic track (tracer.COMM_OVERLAP_TID): the analytic schedule of
        the bucketed quantized reductions inside the dispatched step — the
        window [t0, t1] split proportionally by each bucket's wire bytes.
        Off the main track by construction, so ``dstpu plan`` attributes
        the time as overlapped comm (overlap_fraction) rather than step
        cost, exactly the treatment the prefetch worker's staging gets.
        Hot-path registered: appends only, no device touch."""
        from deepspeed_tpu.telemetry.tracer import COMM_OVERLAP_TID
        comp = self._comm_compress
        window = max(t1 - t0, 0.0)
        end = t0
        for b in self._overlap_meta:
            dur = window * (b["wire_bytes"] / self._overlap_wire_total)
            end += dur
            self.tracer.complete(
                "comm/overlap", dur, cat="comm", end_ts=end,
                tid=COMM_OVERLAP_TID, bucket=b["index"], bytes=b["bytes"],
                wire_bytes=b["wire_bytes"], world=comp.world,
                op="quantized_all_reduce", step=self.global_steps)

    def dump_trace(self, path: Optional[str] = None,
                   tail_s: Optional[float] = None) -> Dict[str, Any]:
        """Write (and return) the dstrace Chrome-trace dump — dispatch /
        drain / prefetch / checkpoint / comm spans plus resilience instant
        events, loadable in ui.perfetto.dev. ``tail_s`` restricts to the
        trailing slice. Also reachable hands-off via ``DSTPU_TRACE=path``
        (dump at exit). See docs/observability.md."""
        return self.tracer.export_chrome(path, tail_s=tail_s)

    def trace_summary(self, prefix: Optional[str] = None) -> Dict[str, Any]:
        """Per-span aggregate (count/total/mean/max/p50/p95/p99 seconds) of
        the tracer ring — the quick in-process look before dumping a
        trace; ``dstpu plan`` on a dump is the full attribution view."""
        return self.tracer.summary(prefix=prefix)

    # ------------------------------------------------------------------
    # dsmem: analytic ledger, live watermarks, OOM forensics
    # ------------------------------------------------------------------
    def _param_count(self) -> int:
        """Model parameter count from host-side metadata (leaf shapes —
        never a device transfer). Under offload_param the device params
        tuple is empty; count the host masters instead."""
        if self._param_offload is not None:
            import math
            try:
                return sum(math.prod(leaf.shape)
                           for leaf in self._param_offload.opt.leaves)
            except Exception:
                return 0
        return sum(int(getattr(x, "size", 0))
                   for x in jax.tree_util.tree_leaves(self.state.params))

    def memory_ledger(self):
        """The analytic dsmem plan for THIS engine's config + mesh (see
        ``deepspeed_tpu/telemetry/memory.py``): per-component bytes and
        per-phase expected HBM/host watermarks. Activation terms need
        shape hints the engine cannot infer generically — model states
        (the dominant preflight term) are exact."""
        from deepspeed_tpu.telemetry.memory import MemoryLedger
        return MemoryLedger.from_config(
            self.config.raw(), num_params=self._param_count(),
            mesh_shape={str(k): int(v) for k, v in self.mesh.shape.items()})

    def _memory_preflight(self, policy: str) -> None:
        """Analytic plan vs device ``bytes_limit`` BEFORE training: a plan
        that cannot fit warns (or raises, ``preflight: refuse``) with the
        next offload tier instead of dying minutes later in XLA with a
        RESOURCE_EXHAUSTED. Skipped on backends without allocator stats
        (CPU: ``memory_stats() is None``)."""
        from deepspeed_tpu.telemetry.memory import (MemoryPreflightError,
                                                    preflight)
        try:
            ledger = self.memory_ledger()
        except Exception:
            logger.exception("dsmem: preflight ledger construction failed")
            return
        limit = 0
        try:
            for s in self.accelerator.memory_stats().values():
                limit = max(limit, int(s.get("bytes_limit", 0)))
        except Exception:
            pass
        if not limit:
            log_dist("dsmem: device reports no bytes_limit (CPU backend?) "
                     "— analytic preflight skipped", ranks=[0])
            return
        verdict = preflight(ledger, limit)
        if verdict["fits"] and not verdict["tight"]:
            return
        sug = verdict.get("suggestion") or {}
        msg = (f"dsmem preflight: plan needs "
               f"{verdict['required_bytes'] / 1e9:.2f}GB HBM at the "
               f"'{verdict['worst_phase']}' watermark vs device limit "
               f"{limit / 1e9:.2f}GB")
        if sug:
            msg += (f"; next tier: {sug['suggestion']} "
                    f"(overrides: {sug['overrides']})")
        if not verdict["fits"] and policy == "refuse":
            raise MemoryPreflightError(msg)
        log_dist(("WARNING: " if not verdict["fits"]
                  else "dsmem preflight (tight headroom): ") + msg,
                 ranks=[0])

    def memory_forensics(self, error: Optional[str] = None,
                         samples: int = 32) -> Dict[str, Any]:
        """Everything the OOM diagnostic bundle embeds: the analytic
        ledger, the last N live samples, per-phase observed watermarks,
        and plan-vs-observed deltas."""
        out: Dict[str, Any] = {
            "error": (error or "")[:2000] or None,
            "global_steps": self.global_steps,
        }
        plan: Dict[str, Any] = {}
        try:
            ledger = self.memory_ledger()
            out["ledger"] = ledger.to_dict()
            plan = ledger.phase_bytes()
        except Exception:
            logger.exception("dsmem: forensics ledger failed")
        if self._mem_sampler is not None:
            # one last observation so the bundle carries the dying state
            try:
                self._mem_sampler.sample(step=self.global_steps)
            except Exception:
                pass
            wm = self._mem_sampler.watermarks()
            out["watermarks"] = wm
            out["samples"] = self._mem_sampler.tail(samples)
            deltas = {}
            for phase, obs in wm.items():
                p = plan.get(phase, {}).get("hbm_bytes")
                o = obs.get("hbm_peak_bytes") or obs.get("hbm_bytes_in_use")
                if p and o:
                    deltas[phase] = round(o / p - 1.0, 4)
            out["plan_vs_observed_delta_frac"] = deltas
        return out

    def _note_oom(self, exc: BaseException) -> None:
        """Dispatch/drain error hook: when the failure classifies as
        RESOURCE_EXHAUSTED, stamp the timeline and stash the forensics
        dict on ``engine.last_oom`` (the resilience runner folds it into
        the diagnostic bundle). Non-OOM errors pass through untouched."""
        from deepspeed_tpu.telemetry.memory import is_oom_error
        if not is_oom_error(exc):
            return
        self.tracer.instant("mem/oom", cat="mem", step=self.global_steps)
        self.last_oom = self.memory_forensics(error=str(exc))
        logger.error("engine: RESOURCE_EXHAUSTED at step %d — memory "
                     "forensics stashed on engine.last_oom",
                     self.global_steps)

    def dump_memory_report(self, path: Optional[str] = None
                           ) -> Dict[str, Any]:
        """Write (and return) the dsmem report artifact — plan + observed
        per-phase watermarks — the input of ``bin/dstpu mem`` (tie-out +
        watermark ratchet vs ``mem_baseline.json``)."""
        from deepspeed_tpu.telemetry.memory import MemorySampler
        sampler = self._mem_sampler
        if sampler is None:
            sampler = MemorySampler(tracer=self.tracer)
        if not sampler.samples:
            sampler.sample(step=self.global_steps)
        try:
            ledger = self.memory_ledger()
        except Exception:
            logger.exception("dsmem: report ledger failed")
            ledger = None
        if path:
            return sampler.export(path, ledger=ledger)
        return sampler.report(ledger=ledger)

    def start_profile_trace(self, log_dir: str) -> None:
        """Start an XLA/TPU profiler trace (reference: NVTX ranges + torch
        profiler hooks; here jax.profiler writes a TensorBoard-viewable trace
        with the engine's named timer scopes)."""
        jax.profiler.start_trace(log_dir)
        log_dist(f"profiler trace started -> {log_dir}", ranks=[0])

    def stop_profile_trace(self) -> None:
        jax.profiler.stop_trace()
        log_dist("profiler trace stopped", ranks=[0])

    def _run_flops_profile(self, stacked_batch):
        """Profile the forward pass at ``profile_step`` (reference: engine.py:1850
        auto-invokes FlopsProfiler). Abstract trace only — no extra device work."""
        from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
        fcfg = self.config.flops_profiler
        micro = jax.tree.map(lambda x: np.asarray(x)[0], stacked_batch)
        prof = FlopsProfiler(self._compute_loss, params=self.state.params)
        prof.stop_profile(self.state.params, micro, self._rng)  # abstract trace only
        prof.print_model_profile(profile_step=self.global_steps,
                                 module_depth=fcfg.module_depth,
                                 top_modules=fcfg.top_modules,
                                 detailed=fcfg.detailed,
                                 output_file=fcfg.output_file)
        self.flops_profiler = prof

    def _advance_data_schedules(self):
        """Advance curriculum/random-LTD schedules at each global step (reference:
        engine curriculum updates + data_pipeline schedulers)."""
        if self.curriculum_scheduler is not None:
            self.curriculum_scheduler.update_difficulty(self.global_steps)
        if self.random_ltd_scheduler is not None:
            self.random_ltd_scheduler.update_seq(self.global_steps)
        if self.compressor is not None:
            self.compressor.set_step(self.global_steps)
            self.compressor.maybe_freeze_masks(self.state.params)
            key = self.compressor.schedule_key()
            if key != self._compression_key:
                # schedule transition (technique activated / bits annealed):
                # drop every compiled step so the next call re-traces with the
                # new static compression structure
                self._compression_key = key
                self._reset_compiled_fns()

    def set_custom_curriculum_learning_schedule(self, schedule_fn):
        """reference: engine.set_custom_curriculum_learning_schedule — install a
        user difficulty function for 'custom' schedule_type."""
        if self.curriculum_scheduler is not None:
            self.curriculum_scheduler.set_custom_get_difficulty(schedule_fn)

    def curriculum_seqlen(self) -> int:
        """Current legacy-curriculum difficulty (seqlen); full seq when disabled."""
        if self.curriculum_scheduler is None:
            raise RuntimeError("curriculum_learning not enabled in config")
        return self.curriculum_scheduler.get_current_difficulty()

    def random_ltd_reserved_length(self) -> int:
        if self.random_ltd_scheduler is None:
            raise RuntimeError("random_ltd not enabled in config")
        return self.random_ltd_scheduler.get_current_seq()

    def _record_metrics(self, out: StepOutput, sync: bool = False):
        """Step-output fan-out. Async pipeline OFF (default) or ``sync=True``
        (host-offload / compat paths, which are host-synchronous by
        construction): today's per-step semantics, device-array
        ``_last_metrics`` + monitor floats at ``steps_per_print`` boundaries.
        Async pipeline ON: the outputs queue on the device-side ring —
        NOTHING is transferred here — and the ring drains (one batched
        ``device_get``) every ``sync_every`` steps."""
        if self._async_enabled and not sync:
            # NOTE: only StepOutput arrays are queued — they are fresh jit
            # outputs. EngineState buffers (e.g. loss_scale.scale) must NOT
            # be captured here: the state is donated to the next compiled
            # step, which deletes those buffers while they'd still sit in
            # the ring. The live scale is fetched at drain time instead.
            due = self._sched.push({
                "step": self.global_steps,
                "samples": self.global_samples,
                "loss": out.loss, "grad_norm": out.grad_norm, "lr": out.lr,
                "overflow": out.overflow,
            })
            if due:
                self._drain_metric_ring()
            return
        self._last_metrics = {"lr": out.lr, "grad_norm": out.grad_norm,
                              "loss": out.loss, "overflow": out.overflow}
        if self._mem_sampler is not None \
                and self.config.memory.sample_on_drain:
            # sync/host-offload paths reach here after the step counter
            # incremented — derive the phase from it (the fused path set it
            # at dispatch; offload paths never dispatch through there)
            self._mem_sampler.phase = ("first_step" if self.global_steps <= 1
                                       else "steady")
            if (self.global_steps % self.config.steps_per_print == 0
                    or not self._mem_sampler.seen(self._mem_sampler.phase)):
                # the print boundary is sync mode's step-boundary sampling
                # cadence (already a host-visible boundary), plus each
                # phase's first step so short runs cover every bucket
                self._mem_sampler.on_drain(step=self.global_steps)
        if self.monitor and self.monitor.enabled:
            events = self._monitor_step_events(
                self.global_steps, self.global_samples, out.loss, out.lr,
                self.state.loss_scale.scale)
            if events:
                self.monitor.write_events(events)

    def _monitor_step_events(self, step, samples, loss, lr, loss_scale):
        """Train/Samples events for one step, gated on the steps_per_print
        boundary — THE single source for both the synchronous record path
        and the async drain (so the two can never log different metrics)."""
        if step % self.config.steps_per_print != 0:
            return []
        events = [("Train/Samples/train_loss", float(loss), samples),
                  ("Train/Samples/lr", float(lr), samples)]
        if self.config.fp16.enabled:
            events.append(("Train/Samples/loss_scale", float(loss_scale),
                           samples))
        return events

    # ------------------------------------------------------------------
    # async step pipeline: the designated drain + its consumers
    # ------------------------------------------------------------------
    # The ring/prefetcher mechanics live on the shared sched core
    # (runtime/sched.py, also consumed by the serve loop); these views keep
    # the names the PR 3 pipeline exposed — consumers and the hot-sync
    # lint fixtures poke them directly.
    @property
    def _metric_ring(self) -> List[Dict[str, Any]]:
        return self._sched.pending

    @property
    def _drained_metrics(self) -> collections.deque:
        return self._sched.drained

    @property
    def _last_drain_time(self) -> Optional[float]:
        return self._sched.anchor

    @_last_drain_time.setter
    def _last_drain_time(self, t: Optional[float]) -> None:
        self._sched.anchor = t

    @property
    def _sync_every(self) -> int:
        return self._sched.sync_every

    @_sync_every.setter
    def _sync_every(self, v: int) -> None:
        self._sched.sync_every = int(v)

    @property
    def _prefetch_depth(self) -> int:
        return self._staged.depth

    @_prefetch_depth.setter
    def _prefetch_depth(self, v: int) -> None:
        self._staged.depth = int(v)

    @property
    def _prefetcher(self) -> Optional[PrefetchLoader]:
        return self._staged.loader

    @property
    def _prefetcher_src(self):
        return self._staged.source

    @property
    def _prefetch_switches(self) -> int:
        return self._staged.switches

    def _drain_metric_ring(self) -> List[Dict[str, Any]]:
        """THE designated readback point of the async pipeline: one batched
        ``device_get`` (DispatchRing.drain) moves every pending step's
        outputs to host (and, by data dependency, proves those steps'
        device work completed — the anchor that keeps the reconciled timers
        honest). Host fan-out: ``_last_metrics``, monitor events for
        ``steps_per_print``-boundary steps, TRAIN_BATCH_TIMER/throughput
        reconciliation, and the ordered entry queue the resilience runner
        replays through its StepGuard."""
        # the LIVE loss scale rides the same transfer (exact at sync_every=1;
        # for lagged fp16 entries the monitor shows the drain-time scale);
        # execution-time OOM of an async step surfaces at the designated
        # readback — same classify-and-stash contract
        try:
            res = self._sched.drain(extra=self.state.loss_scale.scale)
        except Exception as e:
            self._note_oom(e)
            raise
        if res is None:
            return []
        scale = float(res.extra)
        entries = [{"step": int(e["step"]), "samples": int(e["samples"]),
                    "loss": float(e["loss"]),
                    "grad_norm": float(e["grad_norm"]),
                    "lr": float(e["lr"]), "overflow": bool(e["overflow"]),
                    "loss_scale": scale} for e in res.payloads]
        last = entries[-1]
        self._last_metrics = {"lr": last["lr"], "grad_norm": last["grad_norm"],
                              "loss": last["loss"],
                              "overflow": last["overflow"]}
        # window anchor = dispatch of this window's FIRST step (train_batch
        # re-anchors whenever the ring is empty), so checkpoint I/O or idle
        # gaps between windows never inflate the reconciled step time
        window = 0.0
        if res.anchored:
            window = res.window_s
            self.timers(TRAIN_BATCH_TIMER).record_external(
                window, count=len(entries))
            # retro span covering the reconciled window: the TRUE step time
            # of the drained steps (dispatch spans only show launch cost)
            self.tracer.complete("engine/steps_reconciled", window,
                                 cat="train", steps=len(entries),
                                 last_step=last["step"])
        for e in entries:
            if e["overflow"]:
                self.tracer.instant("engine/overflow_step", cat="train",
                                    step=e["step"])
        self.tput_timer.mark_edge()
        if self.monitor and self.monitor.enabled:
            events = []
            for e in entries:
                events.extend(self._monitor_step_events(
                    e["step"], e["samples"], e["loss"], e["lr"],
                    e["loss_scale"]))
            if window > 0:
                events.append(("Train/Samples/steps_per_sec",
                               len(entries) / window, last["samples"]))
            if events:
                self.monitor.write_events(events)
        if self._mem_sampler is not None and self.config.memory.sample_on_drain:
            # the drain already paid a host sync; the dsmem sample here adds
            # allocator-stat dict reads only (DS002-registered hook)
            self._mem_sampler.on_drain(step=last["step"])
        self._sched.store(entries)
        return entries

    def flush_metrics(self) -> List[Dict[str, Any]]:
        """Force-drain the deferred step-output ring (one batched device_get);
        returns the newly drained host entries, [] when nothing is pending.
        Callers use it as a barrier at log/checkpoint boundaries — the
        resilience runner flushes before every save so a checkpoint never
        captures steps its guard has not judged."""
        return self._drain_metric_ring()

    def take_drained_metrics(self) -> List[Dict[str, Any]]:
        """Pop the drained-but-unconsumed host metric entries (ordered, one
        per step: step/samples/loss/grad_norm/lr/overflow/loss_scale). The
        resilience runner's per-step hook — with ``sync_every=N`` its guard
        observes steps with up to N steps of detection lag, replayed in
        order here."""
        return self._sched.take()

    def requeue_drained_metrics(self, entries: List[Dict[str, Any]]) -> None:
        """Put taken-but-unprocessed entries back at the FRONT of the queue
        (original order preserved) — the runner uses this when its guard
        raises mid-replay, so the tail still gets judged by a later flush."""
        self._sched.requeue(entries)

    def configure_async_pipeline(self, enabled: Optional[bool] = None,
                                 sync_every: Optional[int] = None,
                                 prefetch: Optional[bool] = None,
                                 prefetch_depth: Optional[int] = None):
        """Reconfigure the latency-hiding pipeline at runtime (bench sweeps,
        notebooks). The pending ring is flushed FIRST so no step crosses a
        semantics change un-drained. Closing an active prefetcher drops its
        staged batches (the source iterator has already advanced past them)
        — reconfigure at iterator boundaries when exact batch order matters."""
        self.flush_metrics()
        self._staged.close()
        if enabled is not None:
            if enabled and (self._param_offload is not None
                            or self._offload is not None):
                raise ValueError(
                    "async_pipeline cannot be enabled on a host-offload "
                    "engine: the fused host optimizer step is synchronous "
                    "by construction")
            self._async_enabled = bool(enabled)
        if sync_every is not None:
            if int(sync_every) < 1:
                raise ValueError(f"sync_every must be >= 1, got {sync_every}")
            self._sync_every_cfg = int(sync_every)
        # an explicitly-set cadence survives toggling orthogonal knobs
        self._sync_every = self._sync_every_cfg if self._async_enabled else 1
        if prefetch is not None:
            self._prefetch_enabled = bool(prefetch)
        self._prefetch_enabled = self._prefetch_enabled and self._async_enabled
        if self._prefetch_enabled and (self.config.flops_profiler.enabled
                                       or self.config.eigenvalue.enabled):
            log_dist("async_pipeline: prefetch disabled — flops_profiler/"
                     "eigenvalue need host-materialized batches", ranks=[0])
            self._prefetch_enabled = False
        if prefetch_depth is not None:
            self._prefetch_depth = max(1, int(prefetch_depth))
        self.tput_timer.synchronize = not self._async_enabled
        self._last_drain_time = None
        return self

    def _ensure_prefetcher(self, data_iter) -> PrefetchLoader:
        """One staged-batch prefetcher per source iterator (identity-keyed
        by StagedPrefetcher; a new source closes the old prefetcher,
        dropping its staged batches — swap iterators at epoch boundaries)."""
        gas = self.gradient_accumulation_steps

        def stacked_batches():
            while True:
                try:
                    yield self.stack_microbatches(data_iter, gas)
                except StopIteration:   # PEP 479: surface as a clean end
                    return

        def build():
            return PrefetchLoader(
                stacked_batches(),
                stage_fn=lambda b: StagedBatch(
                    self._shard_batch(b, stacked=True)),
                depth=self._prefetch_depth)

        return self._staged.ensure(data_iter, build)

    # ------------------------------------------------------------------
    # forward/backward/step compatibility protocol
    # ------------------------------------------------------------------
    def _build_micro_fns(self):
        cfg = self.config
        tx, lr_schedule = self.tx, self.lr_schedule
        clip, fp16 = cfg.gradient_clipping, cfg.fp16
        grad_shardings = self.param_shardings

        acc_dtype = cfg.grad_accum_dtype

        def fwd_bwd_local(params, batch, rng, scale):
            loss, grads = self._grads_one_micro(params, batch, rng, scale)
            # accumulate in the configured dtype (fp32 default) even when params
            # are compute-dtype shadows (offload mode)
            return loss, jax.tree.map(lambda g: g.astype(acc_dtype), grads)

        # compat path reduces per-microbatch (the reference reduces at each
        # backward when not accumulating); with replica axes the reduce is
        # the int8/sparse-wire collective, one sync per forward/backward pair
        from deepspeed_tpu.runtime.zero.qgz import wrap_grads_phase
        if self._comm_compress is not None:
            # compression without error feedback on the per-microbatch
            # shim: residuals are defined at the accumulation boundary (one
            # reduction per optimizer step), which forward/backward/step
            # does not expose — train_batch() is the EF-carrying path
            wire_axes = self._replica_axes
            _csync = self._comm_compress.make_sync_fn(
                fallback_leaf_sync=self._compress_fallback_sync(wire_axes))

            def sync_fn(grads, batch):
                reduced, _ = _csync(grads, batch, ())
                return reduced
        else:
            wire_axes = self._qgz_axes or self._sparse_grad_axes
            sync_fn = self._make_grad_sync(wire_axes)

        fwd_bwd = wrap_grads_phase(fwd_bwd_local, self.mesh, wire_axes,
                                   self.batch_spec, stacked=False,
                                   sync_fn=sync_fn)

        self._micro_fwd_bwd_fn = watch_jit(jax.jit(
            fwd_bwd, out_shardings=(None, grad_shardings)),
            "engine.micro_fwd_bwd")

        def accum(buf, grads):
            return jax.tree.map(jnp.add, buf, grads)

        self._accum_fn = watch_jit(jax.jit(accum, donate_argnums=(0,),
                                           out_shardings=grad_shardings),
                                   "engine.accum")

        def apply_update(state, grad_sum):
            gas = self.gradient_accumulation_steps
            scale = state.loss_scale.scale
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / (scale * gas), grad_sum)
            return self._update(state, grads, tx, lr_schedule, clip, fp16)

        self._apply_update_fn = watch_jit(jax.jit(
            apply_update, donate_argnums=(0, 1),
            out_shardings=(self.state_shardings, None)),
            "engine.apply_update")

    def _reject_param_offload(self, api: str):
        if self._param_offload is not None:
            raise NotImplementedError(
                f"{api} is not supported with offload_param: the streamed "
                "step cannot keep per-microbatch grads device-resident "
                "between calls — use train_batch()")

    def forward(self, batch) -> jnp.ndarray:
        """Compat shim (reference engine.forward:1838): computes loss AND caches
        grads for the subsequent backward()."""
        self._reject_param_offload("forward()")
        if self._micro_fwd_bwd_fn is None:
            self._build_micro_fns()
        self.timers(FORWARD_GLOBAL_TIMER).start()
        device_batch = self._shard_batch(batch, stacked=False)
        self._rng, r = jax.random.split(self._rng)
        loss, grads = self._micro_fwd_bwd_fn(self.state.params, device_batch, r,
                                             self.state.loss_scale.scale)
        self._pending = (loss, grads)
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    def backward(self, loss=None):
        """Compat shim (reference engine.backward:1977): folds the cached microbatch
        grads into the accumulation buffer."""
        if self._pending is None:
            raise RuntimeError("backward() called without a preceding forward()")
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        _, grads = self._pending
        self._pending = None
        if self._grad_buffer is None:
            self._grad_buffer = grads
        else:
            self._grad_buffer = self._accum_fn(self._grad_buffer, grads)
        self._accum_count += 1
        self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._accum_count >= self.gradient_accumulation_steps

    def step(self):
        """Compat shim (reference engine.step:2176): applies the update at the
        gradient-accumulation boundary; otherwise a no-op. Routes through the
        host offload optimizer when configured (same path as train_batch)."""
        self._reject_param_offload("step()")
        if not self.is_gradient_accumulation_boundary():
            return
        self.timers(STEP_GLOBAL_TIMER).start()
        if self._offload is not None:
            if self._offload_apply_fn is None:
                cfg = self.config
                gas = self.gradient_accumulation_steps

                def finalize(grad_sum, scale):
                    grads = jax.tree.map(
                        lambda g: g.astype(jnp.float32) / (scale * gas), grad_sum)
                    overflow = precision.has_inf_or_nan(grads) \
                        if (cfg.fp16.enabled or self._guard_nonfinite) \
                        else jnp.bool_(False)
                    if cfg.gradient_clipping > 0:
                        grads, norm = precision.clip_by_global_norm(
                            grads, cfg.gradient_clipping)
                    else:
                        norm = precision.global_grad_norm(grads)
                    return grads, norm, overflow

                self._offload_apply_fn = jax.jit(finalize)
            grads, norm, overflow = self._offload_apply_fn(
                self._grad_buffer, self.state.loss_scale.scale)
            self._offload_host_update(jnp.float32(0.0), grads, norm, overflow)
        else:
            if self._apply_update_fn is None:
                self._build_micro_fns()
            self.state, out = self._apply_update_fn(self.state, self._grad_buffer)
            self._record_metrics(out, sync=True)
        self._grad_buffer = None
        self._accum_count = 0
        self.global_steps += 1
        self.global_samples += self.train_batch_size
        self._advance_data_schedules()
        self.timers(STEP_GLOBAL_TIMER).stop()

    # ------------------------------------------------------------------
    # eval
    # ------------------------------------------------------------------
    def compile(self, backend=None, **compile_kwargs):
        """API parity with reference ``engine.compile()``
        (runtime/compiler.py + engine.py compile method). jit is this
        engine's native execution model — every step is already traced once
        and compiled — so this records the request and returns."""
        self._compiled = True
        log_dist("engine.compile(): no-op — the fused train step is already "
                 "jit-compiled (XLA is the native execution model)", ranks=[0])
        return self

    def train(self, mode: bool = True):
        """Module-mode parity (reference nn.Module.train/eval): tracked for
        API compatibility; functional models take determinism via batch/rng
        inputs rather than global module state."""
        self.training = bool(mode)
        return self

    def eval(self):
        return self.train(False)

    def eval_batch(self, batch) -> jnp.ndarray:
        self._reject_param_offload("eval_batch()")
        if self._eval_fn is None:
            def ev(params, batch, rng):
                return self._compute_loss(params, batch, rng)
            self._eval_fn = jax.jit(ev)
        device_batch = self._shard_batch(batch, stacked=False)
        self._rng, r = jax.random.split(self._rng)
        return self._eval_fn(self.state.params, device_batch, r)

    # __call__ mirrors the reference's module-call-through (engine(batch) -> loss)
    def __call__(self, batch):
        return self.forward(batch)

    # ------------------------------------------------------------------
    # introspection (reference engine accessor parity)
    # ------------------------------------------------------------------
    def get_lr(self):
        return [float(jax.device_get(self.lr_schedule(self.state.step)))]

    def get_global_grad_norm(self) -> float:
        v = self._last_metrics.get("grad_norm")
        return float(jax.device_get(v)) if v is not None else 0.0

    def cur_scale(self) -> float:
        return float(jax.device_get(self.state.loss_scale.scale))

    @property
    def skipped_steps(self) -> int:
        return int(jax.device_get(self.state.skipped_steps))

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.micro_batch_size

    def get_params(self):
        if self._param_offload is not None:
            return self._param_offload.masters_tree()
        return self.state.params

    def module_state_dict(self):
        if self._param_offload is not None:
            return self._param_offload.masters_tree()
        return jax.device_get(self.state.params)

    # ------------------------------------------------------------------
    # checkpointing (full engine in deepspeed_tpu/checkpoint)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None):
        """reference: engine.save_checkpoint:3109. Writes ONE logical sharded
        checkpoint (every rank participates; reshape-on-load by construction)."""
        # checkpoint boundary = drain boundary: pending deferred metrics land
        # (monitor/timers/guard consumers) before the state is snapshotted
        sampler = self._mem_sampler
        prev_phase = None
        if sampler is not None:
            prev_phase = sampler.phase
            sampler.phase = "ckpt"     # drain-hook samples land in "ckpt"
        try:
            with self.tracer.span("ckpt/save", cat="ckpt",
                                  step=self.global_steps, tag=tag or "auto"):
                self.flush_metrics()
                from deepspeed_tpu.checkpoint.engine import \
                    save_engine_checkpoint
                return save_engine_checkpoint(self, save_dir, tag=tag,
                                              client_state=client_state or {})
        finally:
            if sampler is not None:
                # the save-time watermark (stage-3 gather buffers, orbax
                # staging) is the "ckpt" phase's ledger counterpart
                sampler.sample(step=self.global_steps)
                sampler.phase = prev_phase

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        strict_provenance: bool = True):
        """reference: engine.load_checkpoint:2763 (+_get_all_zero_checkpoints
        world-size-change handling — free here: the checkpoint is topology-free).

        Mesh-portable by construction: a checkpoint saved at world N restores
        onto this engine's mesh at world M (different dp/fsdp factorization,
        different zero stage/offload tier), re-sharding host-side from the
        parameter-atomic store. ``ds_meta.json`` provenance is checked first:
        a different *model* or a changed global batch (the sampler contract)
        raises ``CheckpointProvenanceError`` — ``strict_provenance=False``
        downgrades the batch-contract check to a warning."""
        from deepspeed_tpu.checkpoint.engine import load_engine_checkpoint
        with self.tracer.span("ckpt/load", cat="ckpt", tag=tag or "latest"):
            out = load_engine_checkpoint(
                self, load_dir, tag=tag,
                load_optimizer_states=load_optimizer_states,
                strict_provenance=strict_provenance)
        # resync data-efficiency schedules to the restored global step; replay the
        # random-LTD token accounting so consumed_layer_tokens survives resume
        if self.random_ltd_scheduler is not None:
            # live training updates at steps 1..N (after each increment); replay
            # 1..N-1 here, _advance_data_schedules covers N
            for step in range(1, self.global_steps):
                self.random_ltd_scheduler.update_seq(step)
        self._advance_data_schedules()
        if self.compressor is not None:
            # restored pruning masks are baked into compiled steps as constants
            # and are NOT part of _compression_key — always re-trace after load
            self._reset_compiled_fns()
        return out
