"""Top-k gating + expert-parallel MoE layer.

Reference analog: ``deepspeed/moe/sharded_moe.py`` — ``TopKGate`` (:449) with
top1/top2/topk gating (:183,:290,:374), capacity, load-balancing aux loss; and
``MOELayer`` (:533): einsum dispatch -> all-to-all -> local experts -> all-to-all ->
combine. Expert groups come from ``utils/groups.py:117``.

TPU-native: GShard-style dense dispatch/combine einsums with the experts dimension
sharded over the ``expert`` mesh axis — XLA emits exactly the all-to-all pair the
reference performs by hand, fused with the dispatch einsums. Static capacity keeps
every shape compile-time constant (no ragged dispatch under jit).
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from deepspeed_tpu.models.llama import shard_activation


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None     # None | "RSample" | "Jitter"
    drop_tokens: bool = True
    use_rts: bool = True                        # random token selection tie-break
    aux_loss_weight: float = 0.01
    router_z_loss_weight: float = 0.001
    # renormalize the kept top-k gate probs to sum to 1 (GShard/Mixtral
    # behavior). HF Qwen2-MoE defaults this OFF (norm_topk_prob=False in
    # Qwen1.5-MoE configs) — raw softmax probs weight the combine directly.
    norm_topk_prob: bool = True
    # int8 wire format for the dispatch/combine collectives (EQuARX-style;
    # cf. reference _AllToAll dispatch, sharded_moe.py:533 + ZeRO++ wire
    # quantization): the token->expert reduction and the expert->token
    # combine run in manual shard_map regions over the batch / expert axes
    # with quantized_psum — 4x less ICI/DCN traffic than fp32 dispatch, 2x
    # vs the default bf16 (plus fp32 per-row scales); straight-through grads
    quantized_dispatch: bool = False
    dtype: Any = jnp.bfloat16


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    cap = int(np.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def top_k_gating(logits, cfg: MoEConfig, capacity: int, rng=None,
                 train: bool = True):
    """Returns (dispatch [T,E,C] bool, combine [T,E,C] float, aux_loss, z_loss).

    reference: top2gating sharded_moe.py:290 — softmax over experts, top-k choice,
    position-in-expert via cumsum, tokens beyond capacity dropped; aux loss =
    E * mean(gate_frac) . mean(token_frac) (switch/gshard load-balancing loss).
    """
    t, e = logits.shape
    if train and cfg.noisy_gate_policy == "RSample" and rng is not None:
        logits = logits + jax.random.normal(rng, logits.shape) / e
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    topk_probs, topk_idx = jax.lax.top_k(probs, cfg.top_k)        # [T, K]

    # aux losses computed on the full softmax (reference: l_aux on gates1)
    top1_onehot = jax.nn.one_hot(topk_idx[:, 0], e, dtype=jnp.float32)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(top1_onehot, axis=0)
    aux_loss = jnp.sum(me * ce) * e * cfg.aux_loss_weight
    z_loss = jnp.mean(jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1) ** 2) * cfg.router_z_loss_weight

    # position of each (token, k) within its expert: cumsum over flattened choices
    # in k-major order so k=0 choices win capacity slots first (reference: gates1
    # positions computed before masking gates2 locations)
    onehot = jax.nn.one_hot(topk_idx, e, dtype=jnp.int32)          # [T, K, E]
    flat = onehot.transpose(1, 0, 2).reshape(cfg.top_k * t, e)     # k-major
    pos_flat = jnp.cumsum(flat, axis=0) - flat                     # [K*T, E]
    pos = pos_flat.reshape(cfg.top_k, t, e).transpose(1, 0, 2)     # [T, K, E]
    pos_in_expert = jnp.sum(pos * onehot, axis=-1)                 # [T, K]
    keep = pos_in_expert < capacity                                # drop overflow

    # normalize kept top-k probs (reference: denom_s = gates1_s + gates2_s);
    # skipped when norm_topk_prob is off (HF Qwen2-MoE semantics)
    kept_probs = topk_probs * keep
    if cfg.norm_topk_prob:
        denom = jnp.maximum(jnp.sum(kept_probs, axis=-1, keepdims=True), 1e-9)
        norm_probs = kept_probs / denom
    else:
        norm_probs = kept_probs

    cap_onehot = jax.nn.one_hot(jnp.where(keep, pos_in_expert, capacity),
                                capacity, dtype=jnp.float32)       # [T, K, C]
    expert_onehot = onehot.astype(jnp.float32)                     # [T, K, E]
    combine = jnp.einsum("tk,tke,tkc->tec", norm_probs, expert_onehot, cap_onehot)
    dispatch = combine > 0
    return dispatch, combine, aux_loss, z_loss


class TopKGate(nn.Module):
    """Router (reference: TopKGate sharded_moe.py:449). fp32 gate weights."""
    cfg: MoEConfig

    @nn.compact
    def __call__(self, x, train: bool = True):
        t = x.shape[0]
        cf = self.cfg.capacity_factor if train else self.cfg.eval_capacity_factor
        capacity = _capacity(t * self.cfg.top_k, self.cfg.num_experts, cf,
                             self.cfg.min_capacity)
        logits = nn.Dense(self.cfg.num_experts, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32, name="wg")(x.astype(jnp.float32))
        rng = self.make_rng("gating") if (train and self.cfg.noisy_gate_policy) else None
        return top_k_gating(logits, self.cfg, capacity, rng=rng, train=train)


class Experts(nn.Module):
    """E parallel SwiGLU expert MLPs, parameters stacked on a leading experts dim
    (reference: moe/experts.py — a ModuleList; here one vmapped dense stack so the
    expert dim shards over the ``expert`` mesh axis)."""
    num_experts: int
    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):  # x: [E, C, D]
        e, c, d = x.shape
        init = nn.initializers.lecun_normal()
        w_gate = self.param("w_gate", init, (self.num_experts, d, self.intermediate_size),
                            jnp.float32)
        w_up = self.param("w_up", init, (self.num_experts, d, self.intermediate_size),
                          jnp.float32)
        w_down = self.param("w_down", init,
                            (self.num_experts, self.intermediate_size, d), jnp.float32)
        x = x.astype(self.dtype)
        g = jnp.einsum("ecd,edf->ecf", x, w_gate.astype(self.dtype))
        u = jnp.einsum("ecd,edf->ecf", x, w_up.astype(self.dtype))
        h = nn.silu(g) * u
        return jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype))


def _quantized_wire_axes(mesh):
    """Axes for the int8 MoE collectives, filtered to what is still automatic
    in the surrounding context (the qgZ gradient phase may already hold the
    data axis manual): (token-reduction axes, expert axis active)."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    tok = tuple(a for a in mesh_lib.batch_axes(mesh)
                if mesh.shape.get(a, 1) > 1 and a not in manual)
    ep = mesh.shape.get("expert", 1) > 1 and "expert" not in manual
    return tok, ep


def _region_mesh(mesh):
    """Mesh to hand a nested shard_map: inside a partial-manual region
    (e.g. the qgZ gradient phase) jax requires the *context* abstract mesh
    (whose outer axes are already Manual), not the concrete one."""
    am = jax.sharding.get_abstract_mesh()
    return am if am.manual_axes else mesh


def _quantized_dispatch_sum(mesh, tok_axes, dispatch, tokens):
    """Token->expert dispatch with int8 on the wire. The SPMD dispatch
    einsum contracts over the token dim, whose shards live on the batch
    axes — the cross-device sum of the per-shard [E,C,D] partials is the
    dispatch collective (reference: _AllToAll before experts,
    sharded_moe.py:533). Here each shard computes its partial locally in a
    manual region and the partials reduce via ``quantized_psum``."""
    from deepspeed_tpu.ops.pallas.quant import quantized_psum

    def body(dm, tk):
        part = jnp.einsum("tec,td->ecd", dm, tk)
        e, c, dd = part.shape
        flat = quantized_psum(part.reshape(e * c, dd), tok_axes)
        return flat.reshape(e, c, dd)

    return jax.shard_map(
        body, mesh=_region_mesh(mesh),
        in_specs=(PartitionSpec(tok_axes), PartitionSpec(tok_axes)),
        out_specs=PartitionSpec(),
        axis_names=frozenset(tok_axes), check_vma=False)(dispatch, tokens)


def _quantized_combine_sum(mesh, combine, expert_out):
    """Expert->token combine with int8 on the wire: each expert shard
    computes its partial [T,D] from its local experts, partials reduce over
    the expert axis via ``quantized_psum`` (the reverse _AllToAll)."""
    from deepspeed_tpu.ops.pallas.quant import quantized_psum

    def body(cm, eo):
        part = jnp.einsum("tec,ecd->td", cm, eo)
        return quantized_psum(part, ("expert",))

    return jax.shard_map(
        body, mesh=_region_mesh(mesh),
        in_specs=(PartitionSpec(None, "expert"), PartitionSpec("expert")),
        out_specs=PartitionSpec(),
        axis_names=frozenset({"expert"}), check_vma=False)(combine, expert_out)


class MOELayer(nn.Module):
    """Dispatch -> experts -> combine (reference: MOELayer sharded_moe.py:533)."""
    cfg: MoEConfig
    hidden_size: int
    intermediate_size: int

    @nn.compact
    def __call__(self, x, train: bool = True):
        """x: [B, S, D] -> ([B, S, D], aux_loss)."""
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)
        dispatch, combine, aux_loss, z_loss = TopKGate(self.cfg, name="gate")(
            tokens, train=train)
        tok_axes, ep_on = (), False
        if self.cfg.quantized_dispatch:
            from deepspeed_tpu.comm import mesh as mesh_lib
            mesh = mesh_lib.get_global_mesh()
            if mesh is not None:
                tok_axes, ep_on = _quantized_wire_axes(mesh)
        # [T,E,C] x [T,D] -> [E,C,D]; experts dim rides the expert mesh axis:
        # XLA inserts the token collective here (reference: _AllToAll before
        # experts) — int8-wire via the manual region when configured
        if tok_axes:
            dispatched = _quantized_dispatch_sum(
                mesh, tok_axes, dispatch.astype(x.dtype), tokens)
        else:
            dispatched = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype),
                                    tokens)
        dispatched = shard_activation(dispatched, ("expert", None, None))
        expert_out = Experts(self.cfg.num_experts, self.hidden_size,
                             self.intermediate_size, self.cfg.dtype,
                             name="experts")(dispatched)
        expert_out = shard_activation(expert_out, ("expert", None, None))
        if ep_on:
            out = _quantized_combine_sum(mesh, combine.astype(x.dtype),
                                         expert_out)
        else:
            out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
        return out.reshape(b, s, d), aux_loss + z_loss


def moe_tensor_rules(path, leaf) -> Optional[PartitionSpec]:
    """Expert-parallel sharding: stacked expert weights shard their leading
    experts dim over the ``expert`` mesh axis (reference: expert params live in
    expert-parallel groups, utils/groups.py:117)."""
    name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
    ndim = np.ndim(leaf)
    if "experts/" in name and ndim == 3:
        return PartitionSpec("expert", None, None)
    return None
