"""Test harness configuration.

Reference analog: ``tests/unit/common.py`` — the reference spawns world_size real
processes per test (DistributedTest) so CI needs no GPUs. Here the same effect is a
virtual 8-device CPU platform (``xla_force_host_platform_device_count=8``): every
test sees 8 JAX devices and exercises real mesh shardings + collectives in one
process. Set BEFORE importing jax anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = os.environ.get("DSTPU_TEST_PLATFORM", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("DSTPU_ACCELERATOR", "cpu")

import jax  # noqa: E402

if os.environ["JAX_PLATFORMS"] == "cpu":
    jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_default_matmul_precision", "highest")

# Persistent XLA compilation cache for the whole session, placed by the rule
# every entry point follows (utils/compile_cache.py): a directory given in
# JAX_COMPILATION_CACHE_DIR is used as it is, never overridden or cleared;
# otherwise the fixed .jax_cache/ of the checkout. The suite compiles the same
# tiny graphs over and over (XLA's in-process cache is per jit instance), so
# the content-addressed cache pays even cold; the thresholds are zeroed
# because those graphs compile in milliseconds. Executables that embed host
# callbacks (pallas interpret mode, io_callback) reload from it without
# trouble on jaxlib 0.9.0 — the per-module allow-list an older jaxlib needed
# is gone.
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _tracer_left_as_found():
    """A test file hands the global tracer on as it found it: a worker runs
    one file after another, and a file that ends with tracing on (the
    benchmark's traced toy cells do) is not the next file's to mend."""
    from deepspeed_tpu.telemetry import get_tracer
    tracer = get_tracer()
    was = tracer.enabled
    yield
    if tracer.enabled != was:
        tracer.configure(enabled=was)


@pytest.fixture
def mesh8():
    """data=2, fsdp=4 mesh over the 8 virtual devices."""
    from deepspeed_tpu.comm.mesh import create_mesh
    from deepspeed_tpu.config.config import MeshConfig
    return create_mesh(MeshConfig(data=2, fsdp=4))


@pytest.fixture
def mesh_dp8():
    from deepspeed_tpu.comm.mesh import create_mesh
    from deepspeed_tpu.config.config import MeshConfig
    return create_mesh(MeshConfig(data=8))


@pytest.fixture(scope="session")
def package_callgraph():
    """The dslint call graph over ``deepspeed_tpu/``, built ONCE per test
    session — the lint-layer tests (hot-path coverage proofs, offline
    purity, reachability assertions) all read from this instead of
    re-parsing ~200 files each."""
    import pathlib as _pathlib

    from deepspeed_tpu.tools.dslint.callgraph import build_graph_from_sources
    from deepspeed_tpu.tools.dslint.engine import iter_python_files

    repo = _pathlib.Path(__file__).resolve().parent.parent
    files = []
    for p in iter_python_files([str(repo / "deepspeed_tpu")]):
        rel = str(_pathlib.Path(p).relative_to(repo)).replace(os.sep, "/")
        files.append((rel, _pathlib.Path(p).read_text(encoding="utf-8")))
    # routes through the dslint snapshot cache: whichever of the engine
    # rules / env_report / this fixture runs first pays for the one build
    return build_graph_from_sources(files)


@pytest.fixture(scope="session")
def hot_reached(package_callgraph):
    """Keys reachable from the declared DS002 hot roots (prune hatches
    applied) — the taint closure the layer tests assert membership in."""
    from deepspeed_tpu.tools.dslint.hotpath import ESCAPE_HATCHES, HOT_ROOTS
    g = package_callgraph
    roots = sorted(filter(None, (g.resolve(r.path, r.qualname)
                                 for r in HOT_ROOTS)))
    prune = {k for k in (g.resolve(h.path, h.qualname)
                         for h in ESCAPE_HATCHES if h.mode == "prune") if k}
    return set(g.reachable_from(roots, prune=prune))
