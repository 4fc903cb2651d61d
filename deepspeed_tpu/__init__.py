"""deepspeed_tpu — a TPU-native distributed training & inference framework.

Capability surface of DeepSpeed (see SURVEY.md), re-designed for TPU: named-axis
device meshes + pjit sharding instead of runtime partition hooks, one fused compiled
train step, Pallas kernels for hot ops, XLA collectives over ICI/DCN.

Public API parity (reference: ``deepspeed/__init__.py``):
- ``initialize(...)`` (:69) → (engine, optimizer, dataloader, lr_scheduler)
- ``init_inference(...)`` (:291)
- ``add_config_arguments(...)`` (:268)
"""

from typing import Any, Callable, Optional

import jax

__version__ = "0.2.0"

from deepspeed_tpu.accelerator import get_accelerator  # noqa: F401
from deepspeed_tpu.comm import mesh as _mesh_lib
from deepspeed_tpu.config.config import DeepSpeedTPUConfig
from deepspeed_tpu.runtime.engine import DeepSpeedTPUEngine
from deepspeed_tpu.utils.logging import log_dist, logger  # noqa: F401


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port: Optional[int] = None,
               mesh=None,
               mpu=None,
               dist_init_required: Optional[bool] = None,
               collate_fn: Optional[Callable] = None,
               config: Any = None,
               config_params: Any = None,
               loss_fn: Optional[Callable] = None,
               example_batch: Any = None,
               tensor_rules: Optional[Callable] = None,
               seed: int = 0):
    """Build the engine (reference: deepspeed.initialize, deepspeed/__init__.py:69).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)`` like the
    reference. ``model`` is a flax Module or a callable
    ``apply_fn(params, batch, rng) -> loss``; ``model_parameters`` is the params
    pytree (or None to init from ``example_batch``).
    """
    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    # initialize() is THE training entry point: an elastic-agent relaunch's
    # escalated-ladder overrides (DSTPU_ELASTIC_CONFIG_OVERRIDES) apply
    # here and only here
    ds_config = config if isinstance(config, DeepSpeedTPUConfig) \
        else DeepSpeedTPUConfig(config, apply_elastic_overrides=True)

    if dist_init_required is None:
        # auto (reference: deepspeed.initialize always ensures the process
        # group, __init__.py:143): join the multi-process rendezvous when a
        # launcher's env (DSTPU_*/torch-style) announces one and the user
        # hasn't already initialized jax.distributed themselves. Mirrors
        # init_distributed's own trigger (num_processes>1 OR a coordinator
        # address alone — launchers may set a subset); discovery runs once
        # and its kwargs are passed through
        disc = _mesh_lib.discover_cluster_env()
        if (not jax.distributed.is_initialized()
                and (disc.get("num_processes", 1) > 1
                     or disc.get("coordinator_address"))):
            _mesh_lib.init_distributed(**disc)
    elif dist_init_required:
        _mesh_lib.init_distributed()

    if mesh is None and mpu is not None:
        # Megatron-style mpu compat (reference: initialize(..., mpu=) —
        # engine.py:1184 reads the mp/pp world sizes off it): translate the
        # mpu's world sizes into a named-axis mesh
        from deepspeed_tpu.config.config import MeshConfig

        def _ws(*names):
            for n in names:
                fn = getattr(mpu, n, None)
                if fn is not None:
                    return int(fn())
            return 1

        mesh = _mesh_lib.create_mesh(MeshConfig(
            tensor=_ws("get_tensor_model_parallel_world_size",
                       "get_model_parallel_world_size"),
            pipe=_ws("get_pipeline_model_parallel_world_size",
                     "get_pipe_parallel_world_size"),
            sequence=_ws("get_sequence_parallel_world_size"),
            data=-1))

    # pipeline dispatch (reference: deepspeed.initialize returns a
    # PipelineEngine when model is a PipelineModule, deepspeed/__init__.py:69)
    from deepspeed_tpu.runtime.pipe.engine import PipeModule, PipelineEngine
    if isinstance(model, PipeModule):
        if lr_scheduler is not None and not callable(lr_scheduler):
            raise ValueError(
                "pipeline: lr_scheduler must be a callable step -> lr "
                f"(got {type(lr_scheduler).__name__}); stateful scheduler "
                "objects are not supported on the pipeline path")
        pipe_engine = PipelineEngine(
            model, config=ds_config, mesh=mesh,
            client_optimizer=optimizer, lr_scheduler=lr_scheduler)
        pipe_loader = None
        if training_data is not None:
            # resolve_batch_sizes guarantees micro_batch_size >= 1 (default 1
            # when the config gives only the accumulation depth)
            import jax as _jax
            from deepspeed_tpu.runtime.dataloader import DeepSpeedTPUDataLoader
            pipe_loader = DeepSpeedTPUDataLoader(
                training_data,
                batch_size=pipe_engine.micro_batch_size *
                pipe_engine.micro_batches,
                collate_fn=collate_fn,
                process_index=_jax.process_index(),
                process_count=_jax.process_count())
        return pipe_engine, pipe_engine.tx, pipe_loader, None

    engine_kwargs = dict(
        model=model,
        config=ds_config,
        params=model_parameters,
        loss_fn=loss_fn,
        mesh=mesh,
        example_batch=example_batch,
        tensor_rules=tensor_rules,
        seed=seed,
        lr_scheduler=lr_scheduler if callable(lr_scheduler) else None,
        client_optimizer=optimizer,
    )
    hybrid_cfg = ds_config.raw().get("hybrid_engine", {})
    if hybrid_cfg.get("enabled", False):
        # RLHF train<->generate engine (reference: deepspeed.initialize returns
        # DeepSpeedHybridEngine when hybrid_engine.enabled)
        from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedTPUHybridEngine
        engine = DeepSpeedTPUHybridEngine(hybrid_config=hybrid_cfg, **engine_kwargs)
    else:
        engine = DeepSpeedTPUEngine(**engine_kwargs)

    dataloader = None
    if training_data is not None:
        from deepspeed_tpu.runtime.dataloader import DeepSpeedTPUDataLoader
        dataloader = DeepSpeedTPUDataLoader(
            training_data,
            batch_size=engine.micro_batch_size * engine.dp_world_size,
            collate_fn=collate_fn,
            process_index=jax.process_index(),
            process_count=jax.process_count())

    return engine, engine.tx, dataloader, engine.lr_schedule


def init_inference(model=None, config=None, params=None, mesh=None,
                   tensor_rules=None, **kwargs):
    """reference: deepspeed.init_inference (deepspeed/__init__.py:291).

    When ``tensor_rules`` is not given and tp_size > 1, AutoTP resolves a policy
    from the model's architecture (reference: auto-injection via
    ``replace_transformer_layer``/``AutoTP``, module_inject/replace_module.py:183).
    """
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import InferenceConfig
    inf_config = config if isinstance(config, InferenceConfig) \
        else InferenceConfig(**(config or {}), **kwargs)
    if tensor_rules is None and inf_config.tp_size > 1:
        from deepspeed_tpu.module_inject.auto_tp import AutoTP
        tensor_rules = AutoTP.infer_rules(model, params=params)
    return InferenceEngine(model, inf_config, params=params, mesh=mesh,
                           tensor_rules=tensor_rules)


def add_config_arguments(parser):
    """reference: deepspeed.add_config_arguments (deepspeed/__init__.py:268)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag for config parsing)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed-TPU json config file")
    return parser
