"""Records ``data/named-serve.xplane.pb`` and ``data/named-serve.events.json``
on a TPU (one process, about a minute): a toy server's ticks and one flash
forward and backward, profiled with the program's tracer on, so that the
trace holds the program's spans in its host plane and the kernels and scopes
under their names. ``test_benchmark_named.py`` reads both files back.

    python tests/benchmarks/record_named_trace.py <output directory>

Nothing here is a measurement: the sizes are toys and the times are kept
only to check that the two clocks and the readers agree.
"""

import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import profile, trace as tr
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_auto
    from deepspeed_tpu.serving.bench_serve import build_tiny_server
    from deepspeed_tpu.telemetry.tracer import get_tracer

    if jax.default_backend() != "tpu":
        print("record_named_trace.py needs a TPU", file=sys.stderr)
        return 1
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    tracer = get_tracer().configure(enabled=True, capacity=1 << 16)

    def attention_loss(q, k, v):
        return flash_attention_auto(q, k, v, causal=True,
                                    window=256).astype(jnp.float32).sum()
    grad = jax.jit(jax.grad(attention_loss, argnums=(0, 1, 2)))
    qkv = [jnp.ones((1, 512, h, 128), jnp.bfloat16) for h in (4, 2, 2)]

    server = build_tiny_server(kv_offload=False, prefix_cache=False).start()
    try:
        def wave(n):
            reqs = [server.submit([3 + i] * (20 + 7 * i), max_new_tokens=6)
                    for i in range(n)]
            return [r.result(timeout=300) for r in reqs]
        wave(3)                                   # compiles
        jax.block_until_ready(grad(*qkv))
        tracer.clear()
        prof = profile.Profiled(tempfile.mkdtemp())
        prof.start()
        wave(3)
        jax.block_until_ready(grad(*qkv))
        prof.stop()
    finally:
        server.stop(drain_timeout=10.0)
    shutil.copy(tr.newest_xplane(prof.directory),
                out / "named-serve.xplane.pb")
    events = [[e[1], e[3], e[4], e[5], e[6], e[7]]
              for e in tracer.events_snapshot()]
    (out / "named-serve.events.json").write_text(json.dumps(
        {"device_kind": jax.devices()[0].device_kind,
         "window": list(prof.window), "sync": prof._sync,
         "events": events}, default=str))
    size = (out / "named-serve.xplane.pb").stat().st_size
    print(f"recorded {len(events)} events and {size} bytes of trace")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
