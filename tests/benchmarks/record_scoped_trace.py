"""Records ``data/scoped-serve.xplane.pb`` and ``data/scoped-serve.events.json``
on a TPU (one process, about a minute): the ticks of a toy server over a
latent cache with routed experts (JoyAI-LLM-Flash's policy at widths the
kernels take: two layers, the first dense), profiled with the program's
tracer on, so that the trace holds every leaf scope of
``telemetry/names.py`` ``SERVED_LEAF_SCOPES`` in its operations' ``tf_op``
(the Pallas calls among them) and the ring the two counts beside
``expert_rows`` and ``latent_panels``. ``test_benchmark_scopes.py`` reads
both files back; ``named-serve.*`` stay as they are.

    python tests/benchmarks/record_scoped_trace.py <output directory>

gzip the trace before it is committed. Nothing here is a measurement: the
sizes are toys and the times are kept only to check that the leaves tile
their parents and the readers agree.
"""

import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def toy_server():
    """A started ``InferenceServer`` over the toy model, bfloat16."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      V2EngineConfig)
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.models.joyai_llm_flash import (JoyAIFlashConfig,
                                                      JoyAIFlashForCausalLM)
    from deepspeed_tpu.runtime.precision import cast_to_compute
    from deepspeed_tpu.serving.server import InferenceServer, ServingConfig

    cfg = JoyAIFlashConfig(
        vocab_size=1024, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_layers=2, num_heads=4,
        q_lora_rank=128, kv_lora_rank=128, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=8,
        num_experts_per_tok=2, max_seq_len=2048)
    params = cast_to_compute(JoyAIFlashForCausalLM(cfg).init(
        jax.random.PRNGKey(0),
        {"input_ids": np.zeros((1, 8), np.int32)})["params"], cfg.dtype)
    engine = InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=64, kv_num_blocks=64,
        scheduler=SchedulerConfig(max_tokens_per_step=256,
                                  prefill_chunk_tokens=256,
                                  prefill_buckets=(128, 256))))
    return InferenceServer(engine, ServingConfig(
        max_queue_depth=32, idle_poll_s=0.001)).start()


def main(out_dir: str) -> int:
    import jax

    from benchmarks.harness import profile, trace as tr
    from deepspeed_tpu.telemetry.tracer import get_tracer

    if jax.default_backend() != "tpu":
        print("record_scoped_trace.py needs a TPU", file=sys.stderr)
        return 1
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    tracer = get_tracer().configure(enabled=True, capacity=1 << 16)
    server = toy_server()
    try:
        def wave():
            # a prompt of two chunks (the second over the first's keys) and
            # one of one, then a few decode steps of both
            reqs = [server.submit([3 + i] * n, max_new_tokens=4)
                    for i, n in enumerate((400, 100))]
            return [r.result(timeout=300) for r in reqs]
        wave()                                    # compiles
        tracer.clear()
        prof = profile.Profiled(tempfile.mkdtemp())
        prof.start()
        wave()
        prof.stop()
    finally:
        server.stop(drain_timeout=10.0)
    shutil.copy(tr.newest_xplane(prof.directory),
                out / "scoped-serve.xplane.pb")
    events = [[e[1], e[3], e[4], e[5], e[6], e[7]]
              for e in tracer.events_snapshot()]
    (out / "scoped-serve.events.json").write_text(json.dumps(
        {"device_kind": jax.devices()[0].device_kind,
         "window": list(prof.window), "sync": prof._sync,
         "events": events}, default=str))
    size = (out / "scoped-serve.xplane.pb").stat().st_size
    print(f"recorded {len(events)} events and {size} bytes of trace")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
