"""CLI tooling tests: nvme tune sweep, ssh fanout, comet monitor backend.

Reference analogs: ``bin/ds_nvme_tune`` (``deepspeed/nvme/perf_sweep``),
``bin/ds_ssh``, ``deepspeed/monitor/comet.py`` — pure-unit (no ssh, no
comet_ml service), mirroring ``tests/unit/launcher`` style.
"""

import json
import os
import subprocess

import numpy as np

from deepspeed_tpu.launcher.nvme_tune import main as nvme_main, sweep
from deepspeed_tpu.launcher.ssh_fanout import fanout, parse_args, run_on_host
from deepspeed_tpu.monitor.monitor import CometMonitor, MonitorMaster


def test_nvme_sweep_measures_and_picks_config(tmp_path, capsys):
    rc = nvme_main(["--nvme_dir", str(tmp_path), "--size_mb", "8",
                    "--threads", "1", "2", "--block_mb", "1", "4",
                    "--trials", "1", "--out", str(tmp_path / "aio.json")])
    assert rc == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    rows = [l for l in lines if "threads" in l]
    assert len(rows) == 4 and all(r["read_gbps"] > 0 for r in rows)
    cfg = json.load(open(tmp_path / "aio.json"))
    assert cfg["aio"]["thread_count"] in (1, 2)
    assert cfg["aio"]["block_size"] % (1 << 20) == 0


def test_ssh_fanout_prefixes_and_aggregates_rc():
    class FakeProc:
        def __init__(self, rc, out):
            self.returncode, self.stdout, self.stderr = rc, out, ""

    def fake_runner(cmd, capture_output, text):
        host = cmd[-2]
        return FakeProc(1 if host == "bad" else 0, f"hello from {host}\n")

    res = fanout(["a", "bad", "c"], ["uptime"], runner=fake_runner)
    assert res["a"][0] == 0 and res["bad"][0] == 1
    host, rc, out, _ = run_on_host("a", ["echo", "x"], runner=fake_runner)
    assert host == "a" and rc == 0 and "hello" in out


def test_ssh_parse_args_remainder():
    a = parse_args(["-H", "/tmp/hosts", "nvidia-smi", "-L"])
    assert a.hostfile == "/tmp/hosts" and a.command == ["nvidia-smi", "-L"]


def test_comet_monitor_gated_and_master_includes_it():
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    cfg = DeepSpeedTPUConfig({"train_batch_size": 8,
                              "comet": {"enabled": False}}, dp_world_size=1)
    mon = CometMonitor(cfg.comet)
    assert not mon.enabled  # disabled config -> no comet_ml import attempted
    master = MonitorMaster(cfg)
    assert any(isinstance(b, CometMonitor) for b in master.backends)
    # enabled but comet_ml not installed -> graceful degrade, not crash
    cfg2 = DeepSpeedTPUConfig({"train_batch_size": 8,
                               "comet": {"enabled": True}}, dp_world_size=1)
    assert not CometMonitor(cfg2.comet).enabled


def test_env_report_checkpoint_status(tmp_path, capsys):
    """dstpu_report --ckpt: latest pointer + per-tag committed/verified/torn
    status for a run dir (the resume-or-not triage view)."""
    import json as _json
    import os as _os

    from deepspeed_tpu.checkpoint.engine import write_manifest, _commit_latest
    from deepspeed_tpu.env_report import checkpoint_report

    run = tmp_path / "run"
    # committed + verified tag
    good = run / "global_step2"
    good.mkdir(parents=True)
    (good / "ds_meta.json").write_text(_json.dumps({"global_steps": 2}))
    write_manifest(str(good))
    _commit_latest(str(run), "global_step2")
    # newer tag, committed but then corrupted (torn)
    torn = run / "global_step4"
    torn.mkdir()
    (torn / "ds_meta.json").write_text(_json.dumps({"global_steps": 4}))
    (torn / "data.bin").write_bytes(b"abcdef")
    write_manifest(str(torn))
    (torn / "data.bin").write_bytes(b"ABCDEF")
    _commit_latest(str(run), "global_step4")
    # uncommitted junk tag
    (run / "global_step9").mkdir()

    summary, tags = checkpoint_report(str(run))
    summary = dict(summary)
    assert summary["latest pointer"] == "global_step4"
    # resume skips the torn tag and falls back to the clean one
    assert summary["resume_from_latest would load"] == "global_step2"
    status = {t.split(" ")[0]: s for t, s in tags}
    assert "TORN" in status["global_step4"]
    assert "committed + verified" in status["global_step2"]
    assert "uncommitted" in status["global_step9"]


def test_env_report_dslint_rows():
    """dstpu_report carries the static-analysis surface: rule count,
    baseline debt, and the DS002 taint summary (roots resolved + closure
    size) so a glance at the report shows whether the lint layer is
    actually covering the hot path."""
    from deepspeed_tpu.env_report import dslint_report

    rows = dict(dslint_report())
    assert int(rows["dslint rules"]) >= 9
    assert rows["dslint baseline"].startswith("0 grandfathered")
    assert "functions" in rows["dslint callgraph"]
    # every declared root must resolve against the shipped tree
    taint = rows["dslint hot taint"]
    resolved, declared = taint.split(" ")[0].split("/")
    assert resolved == declared
    assert "under DS002" in taint
