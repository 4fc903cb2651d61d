"""MiCS + ZeRO++ (hpZ / qwZ) hierarchical sharding tests.

Reference analog: tests/unit/runtime/zero/test_zeropp.py + mics tests —
hierarchical partitioning correctness and parity with plain ZeRO-3 training.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import create_mesh, get_data_parallel_world_size
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.models.simple import SimpleModel, random_batch
from deepspeed_tpu.runtime.zero.partition import (
    build_param_shardings, param_partition_spec, secondary_partition_spec)


def _leaf_specs(shardings):
    return [s.spec for s in jax.tree.leaves(shardings)]


# ---------------------------------------------------------------- spec logic
def test_stage3_spec_covers_full_hierarchical_world():
    spec = param_partition_spec((256, 256), stage=3, fsdp_size=4,
                                fsdp_axes=("fsdp_out", "fsdp"))
    assert ("fsdp_out", "fsdp") in tuple(spec)


def test_mics_spec_inner_axis_only():
    spec = param_partition_spec((256, 256), stage=3, fsdp_size=2,
                                fsdp_axes=("fsdp",))
    assert "fsdp" in tuple(spec) and not any(
        isinstance(e, tuple) and "fsdp_out" in e for e in spec)


def test_secondary_partition_spec_rewrites():
    sec = secondary_partition_spec(PartitionSpec(("fsdp_out", "fsdp"), None))
    assert tuple(sec) == ("fsdp", None)
    sec2 = secondary_partition_spec(PartitionSpec(("tensor", "fsdp_out", "fsdp")))
    assert tuple(sec2) == (("tensor", "fsdp"),)
    # untouched specs pass through
    assert tuple(secondary_partition_spec(PartitionSpec(None, "tensor"))) == \
        (None, "tensor")


# ---------------------------------------------------------------- MiCS engine
def _engine(zero_cfg, mesh_cfg=None, hidden=64, seed=0):
    config = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": zero_cfg,
    }
    if mesh_cfg:
        config["mesh"] = mesh_cfg
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=hidden), config=config,
        example_batch=random_batch(4), seed=seed)
    return engine


def test_mics_splits_mesh_and_shards_inner_only():
    engine = _engine({"stage": 3, "mics_shard_size": 2,
                      "zero_quantized_gradients": True},
                     mesh_cfg={"data": 2, "fsdp": 4})
    assert engine.mesh.shape["fsdp"] == 2 and engine.mesh.shape["fsdp_out"] == 2
    assert get_data_parallel_world_size(engine.mesh) == 8
    for spec in _leaf_specs(engine.param_shardings):
        for entry in spec:
            assert entry != ("fsdp_out", "fsdp")  # never the full world
    # at least one big leaf sharded over the inner axis
    assert any("fsdp" in tuple(s) for s in _leaf_specs(engine.param_shardings))
    # engine-level MiCS+qgZ wiring: the replicated fsdp_out hop joins the
    # replica axes, giving the reference's hierarchical intra->inter reduce
    assert engine._qgz_axes == ("data", "fsdp_out")


@pytest.mark.slow
def test_mics_matches_plain_zero3_training():
    fixed = random_batch(8, seed=0)
    e_mics = _engine({"stage": 3, "mics_shard_size": 2},
                     mesh_cfg={"data": 2, "fsdp": 4})
    e_z3 = _engine({"stage": 3}, mesh_cfg={"data": 2, "fsdp": 4})
    losses_m = [float(e_mics.train_batch(batch=fixed)) for _ in range(5)]
    losses_3 = [float(e_z3.train_batch(batch=fixed)) for _ in range(5)]
    np.testing.assert_allclose(losses_m, losses_3, rtol=2e-4)


# ---------------------------------------------------------------- hpZ engine
def test_hpz_secondary_shardings_built_and_trains():
    """Fast hpZ engine check: shardings + a 3-step loss decrease on a fixed
    batch (full 5-step z3-parity lives in the slow tests)."""
    engine = _engine({"stage": 3, "zero_hpz_partition_size": 2},
                     mesh_cfg={"data": 2, "fsdp": 4})
    assert engine.mesh.shape["fsdp_out"] == 2 and engine.mesh.shape["fsdp"] == 2
    assert engine._secondary_shardings is not None
    # primary params keep the full hierarchical shard (memory), secondary
    # rewrites to inner-only
    prim = _leaf_specs(engine.param_shardings)
    sec = _leaf_specs(engine._secondary_shardings)
    assert any(("fsdp_out", "fsdp") in tuple(p) for p in prim)
    assert not any(("fsdp_out", "fsdp") in tuple(s) for s in sec)
    fixed = random_batch(8, seed=0)
    losses = [float(engine.train_batch(batch=fixed)) for _ in range(3)]
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_hpz_matches_plain_zero3_losses():
    fixed = random_batch(8, seed=0)
    e_hpz = _engine({"stage": 3, "zero_hpz_partition_size": 2},
                    mesh_cfg={"data": 2, "fsdp": 4})
    e_z3 = _engine({"stage": 3}, mesh_cfg={"data": 2, "fsdp": 4})
    losses_h = [float(e_hpz.train_batch(batch=fixed)) for _ in range(5)]
    losses_3 = [float(e_z3.train_batch(batch=fixed)) for _ in range(5)]
    np.testing.assert_allclose(losses_h, losses_3, rtol=2e-4)


@pytest.mark.slow
def test_qwz_quantized_gather_close_to_exact():
    fixed = random_batch(8, seed=0)
    e_q = _engine({"stage": 3, "zero_hpz_partition_size": 2,
                   "zero_quantized_weights": True},
                  mesh_cfg={"data": 2, "fsdp": 4})
    assert e_q._quantized_weights
    e_z3 = _engine({"stage": 3}, mesh_cfg={"data": 2, "fsdp": 4})
    losses_q = [float(e_q.train_batch(batch=fixed)) for _ in range(40)]
    losses_3 = [float(e_z3.train_batch(batch=fixed)) for _ in range(40)]
    # int8 weight gather adds noise (coarse on a 64-wide toy model) but training
    # still converges and the first-step loss matches the exact path closely
    assert losses_q[-1] < 0.5 * losses_q[0], (losses_q[0], losses_q[-1])
    np.testing.assert_allclose(losses_q[0], losses_3[0], rtol=0.05)


def test_qwz_without_hpz_is_ignored():
    engine = _engine({"stage": 3, "zero_quantized_weights": True},
                     mesh_cfg={"data": 2, "fsdp": 4})
    assert not engine._quantized_weights


@pytest.mark.slow
def test_mics_checkpoint_reshape_to_plain_zero3(tmp_path):
    fixed = random_batch(8, seed=0)
    e_mics = _engine({"stage": 3, "mics_shard_size": 2},
                     mesh_cfg={"data": 2, "fsdp": 4})
    for _ in range(3):
        e_mics.train_batch(batch=fixed)
    e_mics.save_checkpoint(str(tmp_path))
    loss_m = float(e_mics.eval_batch(fixed))

    e_z3 = _engine({"stage": 3}, mesh_cfg={"data": 4, "fsdp": 2}, seed=99)
    e_z3.load_checkpoint(str(tmp_path))
    loss_3 = float(e_z3.eval_batch(fixed))
    np.testing.assert_allclose(loss_3, loss_m, rtol=1e-4)


def test_invalid_mics_split_raises():
    with pytest.raises(ValueError):
        _engine({"stage": 3, "mics_shard_size": 3}, mesh_cfg={"data": 2, "fsdp": 4})


@pytest.mark.slow
def test_qgz_stage3_converges_to_parity():
    """zero_quantized_gradients: stage-3 training with int8 gradient
    quantization at the reduction boundary converges like fp gradients
    (reference: all_to_all_quant_reduce, coalesced_collectives.py:31)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.simple import SimpleModel, random_batch

    def train(qgz):
        config = {
            "train_batch_size": 16,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 3,
                                  "zero_quantized_gradients": qgz},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=64), config=config,
            example_batch=random_batch(4))
        assert engine._quantized_gradients == qgz
        fixed = random_batch(16, seed=0)
        return [float(engine.train_batch(batch=fixed)) for _ in range(15)]

    fp = train(False)
    qg = train(True)
    assert qg[-1] < 0.2 * qg[0], qg          # converges
    assert abs(qg[-1] - fp[-1]) < 0.1 + 0.5 * fp[-1], (qg[-1], fp[-1])


def test_qgz_pure_fsdp_fallback_warns():
    """zero_quantized_gradients on a mesh with no replica batch axis saves no
    wire bytes — the engine must say so LOUDLY (UserWarning + logger.warning),
    not fall back silently."""
    with pytest.warns(UserWarning, match="no bytes are saved on the wire|NO "
                                         "bytes"):
        engine = _engine({"stage": 3, "zero_quantized_gradients": True},
                         mesh_cfg={"fsdp": 8})
    assert engine._quantized_gradients and not engine._qgz_axes


def test_qgz_replica_axes_detection():
    """qgZ engages the int8-wire path exactly on the replica batch axes
    (batch-sharded, parameter-free, size>1) — runtime/zero/qgz.py. Pure
    function-level check (the engine wiring is asserted by the wire test);
    a NamedSharding leaf tree stands in for param_shardings."""
    from jax.sharding import NamedSharding
    from deepspeed_tpu.runtime.zero.qgz import replica_grad_axes

    def axes(mesh_cfg, param_spec):
        mesh = create_mesh(MeshConfig(**mesh_cfg))
        shardings = {"w": NamedSharding(mesh, param_spec)}
        return replica_grad_axes(
            mesh, PartitionSpec(("data", "fsdp_out", "fsdp")), shardings)

    # data is a replica axis; fsdp shards params under stage 3
    assert axes({"data": 2, "fsdp": 4},
                PartitionSpec("fsdp", None)) == ("data",)
    # MiCS: params shard over inner fsdp only -> fsdp_out is replica too
    # (the reference's hierarchical intra->inter structure)
    assert axes({"data": 2, "fsdp_outer": 2, "fsdp": 2},
                PartitionSpec("fsdp", None)) == ("data", "fsdp_out")
    # pure-fsdp mesh: no replica axis -> numerics-simulation fallback
    assert axes({"fsdp": 8},
                PartitionSpec(("fsdp_out", "fsdp"), None)) == ()


def test_qgz_wire_is_int8_and_converges_to_parity():
    """The qgZ gradient reduction moves REAL int8 bytes: the lowered train
    step contains all_to_all + all_gather collectives with i8 operands
    (reference: all_to_all_quant_reduce, coalesced_collectives.py:31 — int8
    on the wire, not a numerics round-trip), and training matches fp
    gradients."""
    e_qg = _engine({"stage": 3, "zero_quantized_gradients": True},
                   mesh_cfg={"data": 2, "fsdp": 4})
    e_fp = _engine({"stage": 3}, mesh_cfg={"data": 2, "fsdp": 4})

    e_qg._build_train_batch_fn()
    stacked = jax.tree.map(lambda x: np.asarray(x)[None],
                           random_batch(8, seed=0))
    device_batch = e_qg._shard_batch(stacked, stacked=True)
    txt = e_qg._train_batch_fn.lower(
        e_qg.state, device_batch, jax.random.PRNGKey(0)).as_text()
    a2a_i8 = [ln for ln in txt.splitlines()
              if "all_to_all" in ln and "i8" in ln]
    ag_i8 = [ln for ln in txt.splitlines()
             if "all_gather" in ln and "i8" in ln]
    assert a2a_i8, "gradient reduce-scatter does not carry int8 on the wire"
    assert ag_i8, "gradient regather does not carry int8 on the wire"

    fixed = random_batch(8, seed=0)
    qg = [float(e_qg.train_batch(batch=fixed)) for _ in range(10)]
    fp = [float(e_fp.train_batch(batch=fixed)) for _ in range(10)]
    assert qg[-1] < 0.2 * qg[0], qg
    assert abs(qg[-1] - fp[-1]) < 0.1 + 0.5 * fp[-1], (qg[-1], fp[-1])


def test_qgz_grad_sync_matches_pmean():
    """quantized_grad_sync == pmean within int8 quantization error, on a
    2-axis (hierarchical) manual mesh."""
    from jax.sharding import NamedSharding
    from deepspeed_tpu.runtime.zero.qgz import quantized_grad_sync

    mesh = create_mesh(MeshConfig(data=2, fsdp_outer=2, fsdp=2))
    rng = np.random.default_rng(7)
    # one large leaf (quantized wire) + one tiny leaf (fp pmean)
    big = jnp.asarray(rng.normal(size=(8, 64, 64)), jnp.float32)
    tiny = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)

    def body(b, t):
        out = quantized_grad_sync(
            {"big": b[0], "tiny": t[0]}, ("data", "fsdp_out"))
        return out["big"], out["tiny"]

    f = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(("data", "fsdp_out")),) * 2,
        out_specs=(PartitionSpec(), PartitionSpec()),
        axis_names=frozenset({"data", "fsdp_out"}), check_vma=False))
    # 4 manual groups (data x fsdp_out), one partial per group on dim 0
    big4, tiny4 = big[:4], tiny[:4]
    ob, ot = f(big4, tiny4)
    exact_b = np.asarray(big4).mean(0)
    exact_t = np.asarray(tiny4).mean(0)
    rel = np.abs(np.asarray(ob) - exact_b).max() / np.abs(exact_b).max()
    assert rel < 0.03, rel                      # int8 wire error bound
    np.testing.assert_allclose(np.asarray(ot), exact_t, rtol=1e-5, atol=1e-6)
