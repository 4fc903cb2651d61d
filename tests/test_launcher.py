"""Launcher unit tests (reference shape: tests/unit/launcher/ — arg/hostfile
parsing and runner command construction, no ssh)."""

import base64
import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.launcher import launch as launch_mod
from deepspeed_tpu.launcher import multinode_runner as mnr
from deepspeed_tpu.launcher import runner
from deepspeed_tpu.launcher.constants import (ENV_COORDINATOR,
                                              ENV_NUM_PROCESSES,
                                              ENV_PROCESS_ID)


def test_fetch_hostfile(tmp_path):
    hostfile = tmp_path / "hostfile"
    hostfile.write_text(
        "# comment\n"
        "worker-0 slots=4\n"
        "worker-1 slots=8\n"
        "\n")
    pool = runner.fetch_hostfile(str(hostfile))
    assert pool == {"worker-0": 4, "worker-1": 8}


def test_fetch_hostfile_missing_returns_empty():
    assert runner.fetch_hostfile("/nonexistent/hostfile") == {}


def test_fetch_hostfile_bad_format(tmp_path):
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("worker-0 4\n")
    with pytest.raises(ValueError):
        runner.fetch_hostfile(str(hostfile))


def test_fetch_hostfile_duplicate(tmp_path):
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("w slots=2\nw slots=2\n")
    with pytest.raises(ValueError):
        runner.fetch_hostfile(str(hostfile))


def test_resource_filter_include():
    hosts = {"a": [0, 1, 2, 3], "b": [0, 1, 2, 3]}
    out = runner.parse_resource_filter(hosts, include_str="a@0,2")
    assert out == {"a": [0, 2]}
    out = runner.parse_resource_filter(hosts, include_str="a;b@1")
    assert out == {"a": [0, 1, 2, 3], "b": [1]}


def test_resource_filter_exclude():
    hosts = {"a": [0, 1], "b": [0, 1]}
    out = runner.parse_resource_filter(hosts, exclude_str="b")
    assert out == {"a": [0, 1]}
    out = runner.parse_resource_filter(hosts, exclude_str="b@0")
    assert out == {"a": [0, 1], "b": [1]}


def test_resource_filter_mutually_exclusive():
    with pytest.raises(ValueError):
        runner.parse_resource_filter({"a": [0]}, include_str="a", exclude_str="a")


def test_resource_filter_unknown_host():
    with pytest.raises(ValueError):
        runner.parse_resource_filter({"a": [0]}, include_str="zzz")


def test_world_info_roundtrip():
    info = {"a": [0, 1], "b": [0]}
    encoded = runner.encode_world_info(info)
    assert launch_mod.decode_world_info(encoded) == info


def test_build_rank_env_global_ids():
    world = {"a": [0, 1], "b": [0, 1, 2]}
    env = launch_mod.build_rank_env(world, node_rank=1, local_rank=2,
                                    coordinator_addr="a", coordinator_port=1234)
    assert env[ENV_PROCESS_ID] == "4"  # 2 procs on node a + local_rank 2
    assert env[ENV_NUM_PROCESSES] == "5"
    assert env[ENV_COORDINATOR] == "a:1234"


class _Args:
    def __init__(self, **kw):
        self.user_script = kw.pop("user_script", "train.py")
        self.user_args = kw.pop("user_args", ["--foo", "1"])
        self.coordinator_addr = kw.pop("coordinator_addr", "worker-0")
        self.coordinator_port = kw.pop("coordinator_port", 8476)
        self.nproc_per_node = kw.pop("nproc_per_node", None)
        self.tpu_name = kw.pop("tpu_name", None)
        self.tpu_zone = kw.pop("tpu_zone", None)
        for k, v in kw.items():
            setattr(self, k, v)


def test_pdsh_runner_cmd():
    args = _Args()
    world = runner.encode_world_info({"worker-0": [0], "worker-1": [0]})
    r = mnr.PDSHRunner(args, world)
    cmd = r.get_cmd({"PATH": "/usr/bin"}, {"worker-0": [0], "worker-1": [0]})
    assert cmd[0] == "pdsh"
    assert "-w" in cmd and "worker-0,worker-1" in cmd
    payload = cmd[-1]
    assert "deepspeed_tpu.launcher.launch" in payload
    assert f"--world_info={world}" in payload
    assert "train.py" in payload and "--foo" in payload


def test_ssh_runner_node_cmd():
    args = _Args()
    world = runner.encode_world_info({"h0": [0], "h1": [0]})
    r = mnr.SSHRunner(args, world)
    cmd = r.get_node_cmd("h1", 1, {"XLA_FLAGS": "--foo"})
    assert cmd[0] == "ssh" and "h1" in cmd
    remote = cmd[-1]
    assert "--node_rank=1" in remote
    assert "export XLA_FLAGS=" in remote


def test_gcloud_runner_cmd():
    args = _Args(tpu_name="my-pod", tpu_zone="us-central2-b")
    r = mnr.GcloudTPURunner(args, runner.encode_world_info({}))
    cmd = r.get_cmd({}, {})
    assert cmd[:5] == ["gcloud", "compute", "tpus", "tpu-vm", "ssh"]
    assert "my-pod" in cmd and "--worker=all" in cmd
    assert any(c.startswith("--zone=") for c in cmd)
    assert any(c.startswith("--command=") for c in cmd)


def test_slurm_runner_cmd():
    args = _Args(slurm_comment="")
    world = runner.encode_world_info({"n0": [0], "n1": [0]})
    r = mnr.SlurmRunner(args, world)
    cmd = r.get_cmd({}, {"n0": [0], "n1": [0]})
    assert cmd[0] == "srun" and "-N" in cmd and "2" in cmd


def test_xpk_runner_cmd():
    """GKE multislice dispatch via xpk workload create (the TPU-pod analog
    of the reference SLURM runner; pure command construction)."""
    args = _Args(xpk_cluster="my-cluster", xpk_workload="job1",
                 xpk_docker_image="gcr.io/p/img:latest",
                 tpu_type="v5litepod-256", num_slices=2)
    r = mnr.XpkRunner(args, runner.encode_world_info({}))
    cmd = r.get_cmd({"XLA_FLAGS": "--bar"}, {})
    assert cmd[:3] == ["xpk", "workload", "create"]
    assert "--cluster=my-cluster" in cmd
    assert "--workload=job1" in cmd
    assert "--tpu-type=v5litepod-256" in cmd
    assert "--num-slices=2" in cmd
    assert "--docker-image=gcr.io/p/img:latest" in cmd
    command = [c for c in cmd if c.startswith("--command=")][0]
    assert "train.py" in command and "export XLA_FLAGS=" in command


def test_xpk_cluster_arg_selects_and_validates():
    a = runner.parse_args(["--xpk_cluster", "c1", "--tpu_type",
                           "v5litepod-16", "train.py"])
    assert a.xpk_cluster == "c1" and a.num_slices == 1
    import pytest
    with pytest.raises(ValueError, match="tpu_type"):
        runner.main(["--xpk_cluster", "c1", "train.py"])


def test_mpi_runner_cmd():
    args = _Args()
    world = runner.encode_world_info({"n0": [0], "n1": [0]})
    r = mnr.MPIRunner(args, world)
    cmd = r.get_cmd({"JAX_PLATFORMS": "cpu"}, {"n0": [0], "n1": [0]})
    assert cmd[0] == "mpirun"
    assert "-host" in cmd and "n0,n1" in cmd
    assert "-x" in cmd  # env export


def test_launch_spawns_and_propagates_failure(tmp_path):
    """launch.py kills the group when one child fails (reference launch.py
    signal/monitor loop)."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys, time\n"
        "rank = int(os.environ['DSTPU_PROCESS_ID'])\n"
        "if rank == 1:\n"
        "    sys.exit(3)\n"
        "time.sleep(30)\n")
    world = runner.encode_world_info({"localhost": [0, 1]})
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         f"--world_info={world}", "--node_rank=0",
         "--coordinator_addr=127.0.0.1", "--coordinator_port=9999",
         str(script)],
        cwd="/root/repo", capture_output=True, text=True, timeout=60)
    assert proc.returncode == 3


@pytest.mark.slow
def test_launch_success(tmp_path):
    script = tmp_path / "ok.py"
    script.write_text("print('hello from', __import__('os').environ['DSTPU_PROCESS_ID'])\n")
    world = runner.encode_world_info({"localhost": [0, 1]})
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         f"--world_info={world}", "--node_rank=0",
         "--coordinator_addr=127.0.0.1", "--coordinator_port=9999",
         str(script)],
        cwd="/root/repo", capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0


def test_core_binding_prefix_slices_cores():
    from deepspeed_tpu.launcher.launch import core_binding_prefix
    import os
    n = os.cpu_count() or 1
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) >= 2:
        p0 = core_binding_prefix(0, 2)
        p1 = core_binding_prefix(1, 2)
        assert p0[:2] == ["taskset", "-c"]
        assert p0[2].split(",")[0] == str(cores[0])
        assert p1[2].split(",")[-1] == str(cores[-1])
        # slices are disjoint and only use allowed cores
        s0 = {int(c) for c in p0[2].split(",")}
        s1 = {int(c) for c in p1[2].split(",")}
        assert not (s0 & s1) and (s0 | s1) <= set(cores)
    assert core_binding_prefix(0, len(cores) + 1) == []


def test_discover_cluster_env_chains(monkeypatch):
    from deepspeed_tpu.comm.mesh import discover_cluster_env
    for var in ("DSTPU_NUM_PROCESSES", "DSTPU_PROCESS_ID",
                "DSTPU_COORDINATOR_ADDRESS", "DSTPU_AUTO_MPI_DISCOVERY",
                "WORLD_SIZE", "RANK", "MASTER_ADDR", "MASTER_PORT",
                "OMPI_COMM_WORLD_SIZE", "OMPI_COMM_WORLD_RANK",
                "SLURM_NTASKS", "SLURM_PROCID", "SLURM_NODELIST",
                "SLURM_STEP_NODELIST"):
        monkeypatch.delenv(var, raising=False)
    assert discover_cluster_env() == {}
    monkeypatch.setenv("WORLD_SIZE", "4")
    monkeypatch.setenv("RANK", "2")
    monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
    d = discover_cluster_env()
    assert d == {"num_processes": 4, "process_id": 2,
                 "coordinator_address": "10.0.0.1:29500"}
    # DSTPU_* takes precedence over torch-style
    monkeypatch.setenv("DSTPU_NUM_PROCESSES", "8")
    monkeypatch.setenv("DSTPU_PROCESS_ID", "5")
    d = discover_cluster_env()
    assert d["num_processes"] == 8 and d["process_id"] == 5
    # SLURM fallback
    for var in ("DSTPU_NUM_PROCESSES", "DSTPU_PROCESS_ID", "WORLD_SIZE", "RANK"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.delenv("MASTER_ADDR", raising=False)
    monkeypatch.setenv("SLURM_NTASKS", "16")
    monkeypatch.setenv("SLURM_PROCID", "3")
    monkeypatch.setenv("SLURM_NODELIST", "tpu-pod-node[1-4],tpu-pod-node7")
    # stray SLURM env without opt-in must NOT trigger discovery (a bare
    # python under sbatch would otherwise hang waiting for peers)
    assert discover_cluster_env() == {}
    monkeypatch.setenv("DSTPU_AUTO_MPI_DISCOVERY", "1")
    d = discover_cluster_env()
    assert d["num_processes"] == 16 and d["process_id"] == 3
    assert d["coordinator_address"].startswith("tpu-pod-node1:")
    monkeypatch.delenv("DSTPU_AUTO_MPI_DISCOVERY")
