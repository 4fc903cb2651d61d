"""Device-mesh factory — the TPU-native process-group layer.

Reference analog: ``deepspeed/utils/groups.py`` (dp/mp/ep/sp group factories,
``_create_expert_and_data_parallel:117``, SP accessors ``:472-524``) and
``comm.init_distributed`` / ``initialize_mesh_device`` (``deepspeed/comm/comm.py:619,603``).

On TPU, process groups are *named mesh axes* of one ``jax.sharding.Mesh``:

    axes (outer→inner): ('pipe', 'data', 'fsdp_out', 'fsdp', 'expert',
                         'sequence', 'tensor')

- ``data``     — pure data parallelism (batch sharding, grad all-reduce)
- ``fsdp``     — ZeRO/FSDP parameter+optimizer sharding (reference ZeRO's dp partition)
- ``tensor``   — tensor (Megatron-style) model parallelism; innermost so its
                 collectives ride the fastest ICI links
- ``sequence`` — Ulysses/context parallelism over the sequence dimension
- ``expert``   — MoE expert parallelism (all_to_all dispatch axis)
- ``pipe``     — pipeline stages; outermost so stages map onto distinct ICI
                 sub-slices (or onto DCN slices in multi-slice)

The combined (data × fsdp × sequence) extent is the "seq-dp" world that the reference's
ZeRO runs over (``runtime/engine.py:1190 seq_data_parallel_group``).

Multi-slice: axes named in ``MeshConfig.dcn_axes`` are laid out across slices
(DCN) using ``jax.experimental.mesh_utils.create_hybrid_device_mesh``.
"""

import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.utils.logging import log_dist, logger

# Canonical axis order, outermost (slowest, DCN-friendly) first. ``fsdp_out`` is
# the hierarchical-sharding replica axis (size 1 unless MiCS / ZeRO++ hpZ splits
# the ZeRO world): MiCS shards params over the inner ``fsdp`` sub-axis and
# replicates across ``fsdp_out`` (reference runtime/zero/mics.py:64); hpZ keeps
# the secondary compute shard on ``fsdp`` so per-layer gathers stay node-local
# (reference partition_parameters.py:1664 _partition_param_sec).
MESH_AXES: Tuple[str, ...] = ("pipe", "data", "fsdp_out", "fsdp", "expert",
                              "sequence", "tensor")

# Axes over which a replicated batch is split (DP world for batch-size math).
BATCH_AXES: Tuple[str, ...] = ("data", "fsdp_out", "fsdp")

# The full ZeRO sharding world (what stage 1-3 partition over).
FSDP_AXES: Tuple[str, ...] = ("fsdp_out", "fsdp")

_global_mesh: Optional[Mesh] = None


def resolve_axis_sizes(cfg: MeshConfig, n_devices: int) -> Dict[str, int]:
    """Fill the single -1 axis with the remaining device count; validate product."""
    sizes = {
        "pipe": cfg.pipe, "data": cfg.data,
        "fsdp_out": getattr(cfg, "fsdp_outer", 1), "fsdp": cfg.fsdp,
        "expert": cfg.expert, "sequence": cfg.sequence, "tensor": cfg.tensor,
    }
    unknown = [k for k, v in sizes.items() if v == -1]
    if len(unknown) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {unknown}")
    known = int(np.prod([v for v in sizes.values() if v != -1]))
    if unknown:
        if n_devices % known != 0:
            raise ValueError(
                f"device count {n_devices} not divisible by fixed axes product {known}")
        sizes[unknown[0]] = n_devices // known
    total = int(np.prod(list(sizes.values())))
    if total != n_devices:
        raise ValueError(
            f"mesh axes product {total} != device count {n_devices} (sizes={sizes})")
    return sizes


def create_mesh(cfg: Optional[MeshConfig] = None,
                devices: Optional[Sequence] = None) -> Mesh:
    """Build the named-axis mesh. ``devices`` defaults to all global devices."""
    cfg = cfg or MeshConfig()
    devices = list(devices) if devices is not None else jax.devices()
    sizes = resolve_axis_sizes(cfg, len(devices))
    shape = tuple(sizes[a] for a in MESH_AXES)

    # no flat-reshape fallback: on real chips a failure here is a topology
    # problem (a shape the slice cannot hold, DCN axes on a single slice)
    from jax.experimental import mesh_utils
    dcn_axes = list(cfg.dcn_axes or [])
    if dcn_axes:
        ici_shape = tuple(1 if a in dcn_axes else sizes[a] for a in MESH_AXES)
        dcn_shape = tuple(sizes[a] if a in dcn_axes else 1 for a in MESH_AXES)
        device_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices)
    else:
        device_array = mesh_utils.create_device_mesh(shape, devices=devices)

    mesh = Mesh(device_array, MESH_AXES)
    log_dist(f"created mesh {dict(zip(MESH_AXES, shape))} over {len(devices)} devices",
             ranks=[0])
    return mesh


def get_global_mesh() -> Optional[Mesh]:
    return _global_mesh


def set_global_mesh(mesh: Mesh) -> None:
    global _global_mesh
    _global_mesh = mesh


# --- world-size accessors (reference: utils/groups.py get_*_world_size) -----

def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def get_data_parallel_world_size(mesh: Mesh) -> int:
    """DP world for batch math = data × fsdp_out × fsdp (ZeRO shards inside DP).
    Tolerates user-built meshes that omit the optional fsdp_out axis."""
    return int(np.prod([mesh.shape.get(a, 1) for a in BATCH_AXES]))


def get_seq_data_parallel_world_size(mesh: Mesh) -> int:
    """reference engine.py:1190: ZeRO runs over the seq×dp group under SP."""
    return get_data_parallel_world_size(mesh) * mesh.shape["sequence"]


def get_model_parallel_world_size(mesh: Mesh) -> int:
    return mesh.shape["tensor"]

def get_expert_parallel_world_size(mesh: Mesh) -> int:
    return mesh.shape["expert"]

def get_sequence_parallel_world_size(mesh: Mesh) -> int:
    return mesh.shape["sequence"]

def get_pipe_parallel_world_size(mesh: Mesh) -> int:
    return mesh.shape["pipe"]


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The DP axes present in this mesh — tolerates hand-built meshes that omit
    the optional ``fsdp_out`` axis (NamedSharding rejects unknown axis names)."""
    return tuple(a for a in BATCH_AXES if a in mesh.shape)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a [batch, ...] array: batch split over the DP axes."""
    return NamedSharding(mesh, PartitionSpec(batch_axes(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def discover_cluster_env() -> dict:
    """Rendezvous discovery chain (reference: ``comm/comm.py:619``
    init_distributed env:// + ``mpi_discovery:688`` + the AML/AWS-SM env
    patching ``:744,:776``): DSTPU_* > torch-style RANK/WORLD_SIZE/MASTER_ADDR
    > OpenMPI OMPI_COMM_WORLD_* > SLURM_*. Returns possibly-empty kwargs for
    ``jax.distributed.initialize``."""
    env = os.environ
    out = {}
    # DSTPU_* vars are independent (any launcher may set a subset)
    if "DSTPU_NUM_PROCESSES" in env:
        out["num_processes"] = int(env["DSTPU_NUM_PROCESSES"])
    if "DSTPU_PROCESS_ID" in env:
        out["process_id"] = int(env["DSTPU_PROCESS_ID"])
    if env.get("DSTPU_COORDINATOR_ADDRESS"):
        out["coordinator_address"] = env["DSTPU_COORDINATOR_ADDRESS"]
    if out:
        return out
    # torch-style: the full triple is only ever set together by a launcher, so
    # requiring all three avoids hijacking unrelated runs
    if "WORLD_SIZE" in env and "RANK" in env and env.get("MASTER_ADDR"):
        return {"num_processes": int(env["WORLD_SIZE"]),
                "process_id": int(env["RANK"]),
                "coordinator_address":
                    f"{env['MASTER_ADDR']}:{env.get('MASTER_PORT', '29500')}"}
    # MPI/SLURM allocations leak their env into interactive shells (a bare
    # python under sbatch sees SLURM_NTASKS), so these are opt-in — the analog
    # of the reference's auto_mpi_discovery arg (comm/comm.py:619)
    if env.get("DSTPU_AUTO_MPI_DISCOVERY") != "1":
        return {}
    if "OMPI_COMM_WORLD_SIZE" in env:             # mpirun (mpi_discovery)
        out["num_processes"] = int(env["OMPI_COMM_WORLD_SIZE"])
        out["process_id"] = int(env["OMPI_COMM_WORLD_RANK"])
        if env.get("MASTER_ADDR"):
            out["coordinator_address"] = \
                f"{env['MASTER_ADDR']}:{env.get('MASTER_PORT', '29500')}"
        else:
            # mpirun sets no MASTER_ADDR; the reference bcasts rank 0's IP
            # over MPI (comm.py:688 mpi_discovery) — same here when mpi4py
            # is present, else the user must export MASTER_ADDR
            host = None
            try:
                from mpi4py import MPI
                host = MPI.COMM_WORLD.bcast(_non_loopback_ip(), root=0)
            except Exception as e:   # degrade, never crash startup
                logger.warning(f"OMPI discovery failed ({e})")
            if host:
                out["coordinator_address"] = \
                    f"{host}:{env.get('MASTER_PORT', '29500')}"
            else:
                logger.warning(
                    "OMPI discovery: cannot derive the coordinator address; "
                    "export MASTER_ADDR to rendezvous")
    elif "SLURM_NTASKS" in env and "SLURM_PROCID" in env:   # srun
        out["num_processes"] = int(env["SLURM_NTASKS"])
        out["process_id"] = int(env["SLURM_PROCID"])
        nodelist = env.get("SLURM_STEP_NODELIST", env.get("SLURM_NODELIST", ""))
        head = _slurm_head_node(nodelist)
        if head:
            out["coordinator_address"] = \
                f"{head}:{env.get('MASTER_PORT', '29500')}"
    return out


def _non_loopback_ip() -> str:
    """This host's outbound-interface IP (reference mpi_discovery uses
    ``hostname -I``'s first entry for the same reason:
    gethostbyname(gethostname()) is 127.0.1.1 on stock Debian images)."""
    import socket
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 80))   # no traffic sent; routes the socket
        return s.getsockname()[0]
    except OSError:
        ip = socket.gethostbyname(socket.gethostname())
        return "" if ip.startswith("127.") else ip
    finally:
        s.close()


def _slurm_head_node(nodelist: str) -> str:
    """First hostname of a SLURM nodelist. Handles hyphenated prefixes and
    bracket ranges: ``tpu-pod-node[1-4,7]`` -> ``tpu-pod-node1``."""
    import re
    first = nodelist.split(",")[0].strip()
    m = re.match(r"^([^\[]+)\[(\d+)", first)
    if m:
        return m.group(1) + m.group(2)
    return first


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     deadline_s: Optional[float] = None,
                     retries: Optional[int] = None,
                     backoff_s: Optional[float] = None) -> None:
    """Multi-host bring-up (reference: comm.init_distributed env:// rendezvous,
    comm/comm.py:619). On TPU pods JAX auto-discovers peers from the TPU metadata;
    explicit args support DCN/CPU clusters; env discovery covers torchrun/MPI/
    SLURM launches (``discover_cluster_env``). No-op when single-process.

    The rendezvous is WEDGE-PROOF: it runs under ``comm.guard.bounded_init``
    — a deadline (``deadline_s``, default 300s, env override
    ``DSTPU_COMM_INIT_DEADLINE_S``, 0 = unbounded) turns a hung coordinator
    into a ``CommWedgeError`` instead of an infinite hang, and TRANSIENT
    failures (coordinator not up yet, connection refused/reset) are retried
    with exponential backoff instead of crashing the worker the platform
    just relaunched a second before its peers."""
    disc = discover_cluster_env()
    if num_processes is None:
        num_processes = disc.get("num_processes", 1)
    if coordinator_address is None:
        coordinator_address = disc.get("coordinator_address")
    if process_id is None:
        process_id = disc.get("process_id")
    if num_processes <= 1 and coordinator_address is None:
        return
    kwargs = {}
    if coordinator_address:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id

    from deepspeed_tpu.comm.guard import (INIT_BACKOFF_ENV, INIT_DEADLINE_ENV,
                                          INIT_RETRIES_ENV, bounded_init)

    def _env(name, cast, default):
        try:
            return cast(os.environ.get(name, default))
        except ValueError:
            return cast(default)

    # explicit args win; else the DSTPU_COMM_INIT_* env (exported by the
    # elastic agent from the "comm_guard" config group) configures the
    # rendezvous budget for relaunched workers
    if deadline_s is None:
        deadline_s = _env(INIT_DEADLINE_ENV, float, 300.0)
    if retries is None:
        retries = _env(INIT_RETRIES_ENV, int, 3)
    if backoff_s is None:
        backoff_s = _env(INIT_BACKOFF_ENV, float, 1.0)
    bounded_init(lambda: jax.distributed.initialize(**kwargs),
                 name="jax_distributed", deadline_s=deadline_s,
                 retries=retries, backoff_s=backoff_s)
    # stamp the dstrace process-identity header at rendezvous: every trace
    # this worker dumps from here on carries rank/world, the join key
    # ``dstpu trace merge`` aligns per-rank timelines by
    from deepspeed_tpu.telemetry.tracer import get_tracer
    get_tracer().set_process_identity(jax.process_index(),
                                      jax.process_count())
    log_dist(f"jax.distributed initialized: {jax.process_count()} processes", ranks=[0])
