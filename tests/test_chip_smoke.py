"""Bring-up tests: ``chip_smoke.py``'s three legs at a toy size on the CPU
(kernels in interpret mode), and the rules that keep a run without a chip from
looking like a success — the measurement scripts refuse the CPU, accelerator
detection and the peak table raise instead of guessing, the compile cache is
placed by one rule, the flash backend is partitioned by hand over a mesh, and
an engine gives its memory back.
"""

import gc
import os
import subprocess
import sys
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.models.llama import LlamaConfig  # noqa: E402


@pytest.fixture(scope="module")
def toy():
    """A llama small enough for the CPU with everything the full size has: a
    GQA group, a sliding window shorter than the trained sequence, several
    prompts at once."""
    return chip_smoke.Size(
        model=LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_layers=4, num_heads=4,
                          num_kv_heads=2, max_seq_len=512, sliding_window=32),
        train_layers=2, serve_layers=2, seq_len=128, global_batch=4,
        train_steps=3, prompt_lens=(40, 130, 70), max_new_tokens=4,
        kv_block_size=16, kv_num_blocks=64, interpret=True)


def test_kernels_leg_toy(toy):
    facts = chip_smoke.leg_kernels(toy)
    assert set(facts) == {"flash_fwd_err", "flash_dq_err", "flash_dk_err",
                          "flash_dv_err", "paged_decode_err",
                          "paged_prefill_err", "paged_prefill_short_err"}
    assert all(0 < err <= chip_smoke.KERNEL_TOL for err in facts.values())


def test_trainer_leg_toy_four_devices_match_one(toy):
    """ZeRO-3 over fsdp=4: a quarter of the state on each device, and the
    first-step loss of the one-device run (ZeRO-0, 4 micro-steps) at the same
    global batch."""
    facts = chip_smoke.leg_trainer(toy, jax.devices()[:4])
    assert facts["zero_stage"] == 3 and facts["gas"] == 1
    assert facts["compiles_after_warm"] == 0
    assert facts["losses"][-1] < facts["losses"][0]
    assert max(facts["state_bytes_per_device"]) <= \
        1.1 * facts["state_bytes_total"] / 4
    assert facts["losses"][0] == pytest.approx(
        facts["one_chip_first_loss"], rel=chip_smoke.LAYOUT_LOSS_RTOL)


def test_trainer_leg_fails_when_loss_does_not_fall(toy, monkeypatch):
    """A leg's check is a failure, not a note: with no learning rate the loss
    stays where it was and the leg raises."""
    import deepspeed_tpu
    real = deepspeed_tpu.initialize

    def frozen(**kw):
        kw["config"]["optimizer"]["params"]["lr"] = 0.0
        return real(**kw)
    monkeypatch.setattr(deepspeed_tpu, "initialize", frozen)
    with pytest.raises(chip_smoke.SmokeError, match="did not fall"):
        chip_smoke.leg_trainer(toy, jax.devices()[:1])


def test_server_leg_toy(toy):
    facts = chip_smoke.leg_server(toy)
    assert facts["requests"] == 2 + len(toy.prompt_lens)
    assert facts["step_faults"] == 0


@pytest.mark.parametrize("server_fails", [False, True])
def test_main_ends_stdout_with_the_result_line(monkeypatch, capsys,
                                               server_fails):
    """The driver parses the last line of stdout: exactly ``ok`` and
    ``device`` = {platform, kind, count}. Every other fact is on the summary
    line before it. A leg that fails ends stdout with ``"ok": false`` and the
    error still comes out."""
    import json

    from deepspeed_tpu import accelerator
    from deepspeed_tpu.utils import compile_cache

    def server(size):
        if server_fails:
            raise chip_smoke.SmokeError("a request did not finish")
        return {"requests": 8}
    monkeypatch.setattr(accelerator, "require_tpu",
                        lambda who: jax.devices()[:1])
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "/given/from/outside")
    monkeypatch.setattr(chip_smoke, "leg_kernels", lambda size: {"err": 1e-3})
    monkeypatch.setattr(chip_smoke, "leg_trainer",
                        lambda size, devices: {"chips": len(devices)})
    monkeypatch.setattr(chip_smoke, "leg_server", server)
    if server_fails:
        with pytest.raises(chip_smoke.SmokeError):
            chip_smoke.main()
    else:
        assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    dev = jax.devices()[0]
    assert json.loads(lines[-1]) == {
        "ok": not server_fails,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": 1}}
    assert lines[0].startswith("chip_smoke: {'platform'")
    if not server_fails:
        tag = "chip_smoke: summary "
        assert lines[-2].startswith(tag)
        summary = json.loads(lines[-2][len(tag):])
        assert set(summary["legs"]) == {"kernels", "trainer", "server"}
        assert summary["claim"] is None and "peak_hbm_bytes" in summary


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py",
                                    "bench_decode.py"])
def test_scripts_refuse_the_cpu(script):
    """No chip: one line on stderr, nothing on stdout, a nonzero exit — in
    seconds, before any model is built."""
    out = subprocess.run([sys.executable, script], cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr and len(out.stderr.splitlines()) == 1


def test_accelerator_detection_raises_on_unknown_platform(monkeypatch):
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.accelerator.cpu_accelerator import CPUAccelerator

    class Dev:
        platform = "gpu"
    monkeypatch.delenv("DSTPU_ACCELERATOR", raising=False)
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
    with pytest.raises(RuntimeError, match="no accelerator for jax platform"):
        real_accelerator._detect()

    def broken():
        raise RuntimeError("backend would not start")
    monkeypatch.setattr(jax, "local_devices", broken)
    with pytest.raises(RuntimeError, match="would not start"):
        real_accelerator._detect()             # the probe's own error, kept
    monkeypatch.setattr(jax, "local_devices", lambda: jax.devices("cpu"))
    assert isinstance(real_accelerator._detect(), CPUAccelerator)


def test_peak_table_raises_on_unknown_device_or_dtype(monkeypatch):
    from deepspeed_tpu.accelerator.cpu_accelerator import CPUAccelerator
    from deepspeed_tpu.accelerator.tpu_accelerator import TPUAccelerator

    class Dev:
        device_kind = "TPU v5 lite"
    acc = TPUAccelerator()
    monkeypatch.setattr(acc, "devices", lambda: [Dev()])
    assert acc.peak_tflops("bf16") == 197.0
    assert acc.peak_tflops("int8") == 393.0
    with pytest.raises(ValueError, match="no published peak"):
        acc.peak_tflops("fp8")                 # v5e has no fp8 path
    Dev.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="no published peak"):
        acc.peak_tflops("bf16")
    with pytest.raises(ValueError, match="no published peak"):
        CPUAccelerator().peak_tflops()


def test_compile_cache_rule(monkeypatch, tmp_path):
    """Given from outside: the code sets nothing. Not given: the one fixed,
    git-ignored directory of the checkout."""
    from deepspeed_tpu.utils import compile_cache
    set_to = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda key, value: set_to.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert set_to == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert set_to == [("jax_compilation_cache_dir", fixed)]
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                             cwd=REPO)
    assert ignored.returncode == 0


@pytest.mark.parametrize("window", [None, 16])
def test_flash_backend_over_a_mesh_matches_xla(window):
    """A Mosaic kernel cannot be partitioned automatically, so over a mesh the
    flash backend runs per shard of batch rows and heads; the result and the
    gradients are those of the plain attention, window included."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models.llama import (_dispatch_attention,
                                            _xla_attention)
    before = mesh_lib.get_global_mesh()
    mesh_lib.set_global_mesh(mesh_lib.create_mesh(
        MeshConfig(data=2, fsdp=2, tensor=2)))
    try:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(8, 64, 4, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(8, 64, 2, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(8, 64, 2, 16)), jnp.float32)

        def flash(q, k, v):
            return _dispatch_attention("flash", q, k, v, window=window)

        def plain(q, k, v):
            return _xla_attention(q, k, v, True, None, window=window)

        out = jax.jit(flash)(q, k, v)
        assert out.sharding.spec == jax.sharding.PartitionSpec(
            ("data", "fsdp"), None, "tensor")
        np.testing.assert_allclose(out, plain(q, k, v), atol=2e-5)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a) ** 2),
                               argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2),
                        argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-4)
        # the params-init trace: 2 rows do not split over 4 batch shards
        np.testing.assert_allclose(jax.jit(flash)(q[:2], k[:2], v[:2]),
                                   plain(q[:2], k[:2], v[:2]), atol=2e-5)
    finally:
        mesh_lib.set_global_mesh(before)


def test_activation_sharding_is_applied_and_raises(mesh8):
    """``shard_activation`` constrains for real (a bare PartitionSpec used to
    raise outside a mesh context and the error was swallowed, so nothing was
    ever constrained) and no longer hides what jax refuses."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models.llama import BATCH_AXES, shard_activation
    before = mesh_lib.get_global_mesh()
    mesh_lib.set_global_mesh(mesh8)
    try:
        out = jax.jit(lambda x: shard_activation(x, (BATCH_AXES, None)))(
            jnp.ones((8, 4)))
        assert out.sharding.spec[0] == ("data", "fsdp")
        # one axis on two dimensions: jax's own error comes through
        with pytest.raises(Exception, match="duplicate entries"):
            shard_activation(jnp.ones((8, 4)), (BATCH_AXES, "fsdp"))
    finally:
        mesh_lib.set_global_mesh(before)


def test_paged_kernel_cuts_a_tall_fold_into_row_blocks():
    """A 1024-token chunk of a 4-way group is 4096 fold rows: two row blocks,
    each with its own causal horizon and window floor."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    rng = np.random.default_rng(0)
    hkv, d, bs, nb, t, mb = 1, 128, 64, 40, 1024, 32
    assert pa._tile(4 * t, mb, bs, d, 4, hkv)[0] == pa._MAX_ROWS < 4 * t
    kp = jnp.asarray(rng.normal(size=(hkv, nb, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(hkv, nb, bs, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, t, 4, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb - 1)[:mb].reshape(1, mb),
                         jnp.int32)
    for start, window in ((512, 700), (0, None)):
        st = jnp.full((1,), start, jnp.int32)
        out = pa.paged_attention(q, kp, vp, tables, st, window=window,
                                 interpret=True)
        ref = pa.paged_attention_reference(q, kp, vp, tables, st,
                                           window=window)
        np.testing.assert_allclose(out, ref, atol=2e-5)


def test_engine_is_collectable_after_a_step():
    """The compile ledger's wrapper must not pin the jitted step: an engine
    dropped after training frees its device state."""
    import deepspeed_tpu
    from deepspeed_tpu.models.simple import SimpleModel, random_batch

    def run():
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=16),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}},
            example_batch=random_batch(8))
        engine.train_batch(batch=random_batch(8))
        return weakref.ref(engine)
    ref = run()
    gc.collect()
    assert ref() is None
