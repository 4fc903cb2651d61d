"""Multi-process distributed test harness.

Reference analog: ``tests/unit/common.py:416`` (``DistributedTest``) — the
reference's key testing trick: every distributed test spawns ``world_size``
*real processes* on one host (``_launch_daemonic_procs:170``), rendezvous over
TCP, runs the test body in every rank (``_dist_run:279``), and propagates
failures/skips back through the pool with a timeout kill.

TPU redesign: single-process multi-device SPMD already covers sharding
semantics (tests/conftest.py), so this harness exists for what that cannot
exercise — the *multi-host* path: ``jax.distributed.initialize`` rendezvous,
cross-process global meshes, and gloo-backed CPU collectives standing in for
ICI/DCN (the same substitution the reference makes with gloo for NCCL).
``run_distributed`` launches N python processes, each contributing
``devices_per_process`` virtual CPU devices to one global mesh; the target
function must be importable (``module:qualname``) and runs in every rank.
"""

import os
import socket
import subprocess
import sys
import time
from typing import Callable, Optional, Sequence, Union

DEFAULT_TIMEOUT = 240

_BOOTSTRAP = r"""
import importlib, os, sys
for p in os.environ.get("DSTPU_TEST_PATH", "").split(os.pathsep):
    if p and p not in sys.path:
        sys.path.insert(0, p)
# the parent pytest env carries conftest's 8-device XLA flag; this rank's own
# device count replaces it
ndev = os.environ["DSTPU_TEST_LOCAL_DEVICES"]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f)
import jax
jax.config.update("jax_num_cpu_devices", int(ndev))
jax.config.update("jax_cpu_collectives_implementation", "gloo")
from deepspeed_tpu.comm.mesh import init_distributed
# the wedge-proof rendezvous: deadline + transient-retry (comm/guard.py
# bounded_init) — a dead coordinator fails the rank with CommWedgeError
# inside the deadline instead of hanging the whole harness to its timeout
init_distributed(
    coordinator_address=os.environ["DSTPU_TEST_COORD"],
    num_processes=int(os.environ["DSTPU_TEST_NPROC"]),
    process_id=int(os.environ["DSTPU_TEST_RANK"]))
mod_name, _, qual = os.environ["DSTPU_TEST_FN"].partition(":")
fn = importlib.import_module(mod_name)
for part in qual.split("."):
    fn = getattr(fn, part)
fn()
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_distributed(fn: Union[Callable, str], world_size: int = 2,
                    devices_per_process: int = 2,
                    timeout: float = DEFAULT_TIMEOUT,
                    env: Optional[dict] = None) -> Sequence[str]:
    """Run ``fn`` in ``world_size`` fresh processes under one jax.distributed
    rendezvous. ``fn`` is a module-level callable or an ``"module:qualname"``
    string. Returns per-rank stdout; raises RuntimeError with the failing
    rank's output on any nonzero exit (reference ``_dist_run`` failure
    propagation) or TimeoutError after ``timeout`` (reference
    ``DS_UNITTEST_TIMEOUT`` kill)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    extra_paths = [repo_root]
    if callable(fn):
        mod = getattr(fn, "__module__", None)
        qual = getattr(fn, "__qualname__", None)
        if not mod or not qual or "<locals>" in qual:
            raise ValueError("fn must be importable (module-level) to run in "
                             "spawned ranks")
        if "." in mod:
            # dotted (package) module: import it by its real name in the child
            # — re-importing under a stripped name would double-import it and
            # put package internals on sys.path
            import importlib.util
            try:
                if importlib.util.find_spec(mod) is None:
                    raise ValueError(f"module {mod!r} is not importable from "
                                     "a spawned rank")
            except ImportError:
                raise ValueError(f"module {mod!r} is not importable from a "
                                 "spawned rank") from None
        else:
            # top-level module (e.g. a pytest-loaded test file): make its own
            # directory importable in the child
            mod_file = getattr(sys.modules.get(mod), "__file__", None)
            if mod_file:
                extra_paths.append(os.path.dirname(os.path.abspath(mod_file)))
        fn = f"{mod}:{qual}"

    coord = f"127.0.0.1:{free_port()}"
    procs = []
    for rank in range(world_size):
        rank_env = dict(os.environ,
                        DSTPU_TEST_COORD=coord,
                        DSTPU_TEST_NPROC=str(world_size),
                        DSTPU_TEST_RANK=str(rank),
                        DSTPU_TEST_LOCAL_DEVICES=str(devices_per_process),
                        DSTPU_TEST_FN=fn,
                        DSTPU_TEST_PATH=os.pathsep.join(extra_paths),
                        **(env or {}))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _BOOTSTRAP], env=rank_env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=repo_root))

    # drain all ranks concurrently: a rank blocking on a full stdout pipe would
    # stall its collectives and masquerade as a hang of its peers
    import threading
    outs = [None] * world_size

    def drain(rank, p):
        outs[rank], _ = p.communicate()

    readers = [threading.Thread(target=drain, args=(r, p), daemon=True)
               for r, p in enumerate(procs)]
    for t in readers:
        t.start()
    deadline = time.time() + timeout
    try:
        for t in readers:
            t.join(max(0.0, deadline - time.time()))
        timed_out = [r for r, t in enumerate(readers) if t.is_alive()]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for t in readers:
        t.join(10)
    # a rank that crashed while its peers hung in a collective is the root
    # cause — report its traceback, not the peers' timeout
    for rank, p in enumerate(procs):
        if p.returncode not in (0, None) and rank not in timed_out:
            raise RuntimeError(
                f"rank {rank} exited {p.returncode}:\n{outs[rank]}")
    if timed_out:
        raise TimeoutError(f"ranks {timed_out} timed out ({timeout}s)")
    return outs


class DistributedTest:
    """Class-style sugar matching the reference spelling: subclass, set
    ``world_size``, point ``run = staticmethod(body_fn)`` at a module-level
    body, call ``self.launch()`` from a normal pytest test."""

    world_size: int = 2
    devices_per_process: int = 2
    timeout: float = DEFAULT_TIMEOUT
    run: Callable = None

    def launch(self):
        return run_distributed(type(self).run, self.world_size,
                               self.devices_per_process, self.timeout)
