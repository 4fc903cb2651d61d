"""``dstpu_serve`` — stand up the serving stack on one host.

Builds a model from a named preset (random-init unless a checkpoint is
supplied), wraps it in ``InferenceEngineV2`` + ``InferenceServer`` + the HTTP
front-end, and serves until SIGINT (which triggers a graceful drain). The
hermetic CPU default (``--preset tiny``) is the zero-to-first-token path:

    dstpu_serve --port 8000 &
    curl -s localhost:8000/generate -d '{"prompt_tokens": [1,2,3]}'

``build_server`` is the one function that turns a model config into a server;
``chip_smoke.py`` builds its full-width server with it too.
"""

import argparse
import signal
import sys


def resolve_preset(name: str):
    """A ``LlamaConfig`` by name: ``tiny``, or a preset constant of
    ``models/llama.py`` or ``models/families.py`` (e.g. ``MISTRAL_7B``)."""
    from deepspeed_tpu.models import families, llama
    if name == "tiny":
        return llama.TINY_LLAMA
    for module in (llama, families):
        cfg = getattr(module, name, None)
        if isinstance(cfg, llama.LlamaConfig):
            return cfg
    raise ValueError(f"unknown preset {name!r}")


def build_server(cfg, engine_config=None, serving_config=None,
                 checkpoint=None, seed: int = 0):
    """``InferenceServer`` (not started) over an ``InferenceEngineV2`` for
    ``cfg``. Parameters come from ``checkpoint`` (a bare fp32 params npz in
    the universal format) or a seeded random init, and are held in
    ``cfg.dtype``: left in fp32 the weights take twice the memory the config
    asks for."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.runtime.precision import cast_to_compute
    from deepspeed_tpu.serving.server import InferenceServer

    if checkpoint:
        # training checkpoints carry optimizer state and need an engine;
        # the serving path wants the flat "a/b/c" keys re-nested into a tree
        from deepspeed_tpu.checkpoint.universal import load_fp32_state
        params = {}
        for key, arr in load_fp32_state(checkpoint).items():
            node = params
            *parents, leaf = key.split("/")
            for part in parents:
                node = node.setdefault(part, {})
            node[leaf] = arr
        params = cast_to_compute(params, cfg.dtype)    # on the host
    else:
        # init and cast in one program: the fp32 tree never exists whole
        model = LlamaForCausalLM(cfg)
        batch = {"input_ids": np.zeros((1, 8), np.int32)}
        params = jax.jit(lambda key: cast_to_compute(
            model.init(key, batch)["params"], cfg.dtype))(
                jax.random.PRNGKey(seed))
    engine = InferenceEngineV2(params, cfg, engine_config)
    return InferenceServer(engine, serving_config)


def main(argv=None):
    p = argparse.ArgumentParser(prog="dstpu_serve", description=__doc__)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--preset", default="tiny",
                   help="model preset: tiny (CPU demo) or a name from "
                        "models/llama.py or models/families.py "
                        "(e.g. MISTRAL_7B)")
    p.add_argument("--checkpoint", default=None,
                   help="msgpack/orbax params path (random init when unset)")
    p.add_argument("--max-queue-depth", type=int, default=256)
    p.add_argument("--max-new-tokens", type=int, default=64,
                   help="default per-request generation budget")
    p.add_argument("--kv-num-blocks", type=int, default=512)
    p.add_argument("--kv-block-size", type=int, default=64)
    p.add_argument("--kv-high-watermark", type=float, default=0.95)
    p.add_argument("--request-timeout-s", type=float, default=None)
    p.add_argument("--kv-offload", action="store_true",
                   help="enable the host-RAM KV offload tier (overload "
                        "demotes queued/idle requests' KV pages to host "
                        "RAM instead of rejecting)")
    p.add_argument("--host-kv-budget-mb", type=int, default=256,
                   help="host-RAM budget for demoted KV pages")
    p.add_argument("--brownout-pressure", type=float, default=0.85,
                   help="degradation-ladder brownout threshold")
    p.add_argument("--shed-pressure", type=float, default=0.97,
                   help="degradation-ladder shed (429) threshold")
    p.add_argument("--brownout-max-new-tokens", type=int, default=16,
                   help="per-request generation cap while browned out")
    args = p.parse_args(argv)

    from deepspeed_tpu.inference.v2.engine_v2 import V2EngineConfig
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    try:
        cfg = resolve_preset(args.preset)
    except ValueError as e:
        p.error(str(e))
    enable_compile_cache()
    server = build_server(
        cfg,
        V2EngineConfig(kv_block_size=args.kv_block_size,
                       kv_num_blocks=args.kv_num_blocks),
        ServingConfig(
            max_queue_depth=args.max_queue_depth,
            default_max_new_tokens=args.max_new_tokens,
            default_timeout_s=args.request_timeout_s,
            kv_high_watermark=args.kv_high_watermark,
            kv_offload_enabled=args.kv_offload,
            host_kv_budget_bytes=args.host_kv_budget_mb << 20,
            brownout_pressure=args.brownout_pressure,
            shed_pressure=args.shed_pressure,
            brownout_max_new_tokens=args.brownout_max_new_tokens),
        checkpoint=args.checkpoint).start()
    frontend = ServingFrontend(server, host=args.host, port=args.port).start()
    print(f"dstpu_serve: {frontend.url} (preset={args.preset}, "
          f"kv_blocks={args.kv_num_blocks})", flush=True)

    import threading
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    print("dstpu_serve: draining...", flush=True)
    server.stop(drain_timeout=30.0)
    frontend.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
