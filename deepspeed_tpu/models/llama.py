"""Llama-family causal LM — the flagship training model.

Reference analog: the reference has no in-tree Llama *training* model (it wraps HF
modules), but its inference stack ships per-arch implementations
(``deepspeed/inference/v2/model_implementations/llama_v2``,
``module_inject/containers/llama.py``). Here the model is first-class and TPU-native:

- pure flax, bf16-friendly; matmuls land on the MXU
- Megatron-style tensor parallelism expressed as *sharding rules*
  (``llama_tensor_rules``), not module surgery — the AutoTP analog
  (``module_inject/auto_tp.py:189``) for our own model zoo
- activation sharding constraints on the (batch, sequence, heads) axes so XLA lays
  collectives on the right mesh axes
- pluggable attention backend: "xla" (fused by the compiler), "flash" (Pallas),
  "ulysses" (all-to-all SP, reference ``sequence/layer.py:271``), "ring"
  (blockwise CP — the reference gap noted in SURVEY.md §2.2)
- optional ``lax.scan`` over layers (fast compiles at depth) + jax.checkpoint remat
  policies (reference ``runtime/activation_checkpointing``)
"""

import dataclasses
from functools import lru_cache, partial
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

# logical activation axes -> mesh axes
from deepspeed_tpu.comm.mesh import BATCH_AXES  # ("data", "fsdp_out", "fsdp")
SEQ_AXIS = "sequence"
HEADS_AXIS = "tensor"


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    attention_backend: str = "xla"     # xla | flash | ulysses | ring
    remat: bool = False
    remat_policy: str = "nothing_saveable"
    scan_layers: bool = False
    logits_soft_cap: Optional[float] = None
    # fuse the lm-head matmul with softmax-CE per token-chunk so the fp32
    # [B*S, V] logits tensor never materializes (see
    # sequence/cross_entropy.py:chunked_cross_entropy). None = dense loss.
    loss_chunk_size: Optional[int] = None
    # unroll the chunk loop instead of scan(checkpoint) — the scan structure
    # is suspected of pathological XLA:TPU compile times when nested in the
    # engine's gas scan
    loss_chunk_unroll: bool = False
    # llama-family arch knobs (mistral/qwen2/phi3 are llama variants):
    attention_bias: bool = False          # qwen2: bias on q/k/v projections
    sliding_window: Optional[int] = None  # mistral: attend to last W tokens only
    # gemma-family knobs (gemma/gemma2 are llama variants too):
    hidden_act: str = "silu"              # gemma: "gelu_tanh" gated MLP
    rms_scale_offset: bool = False        # gemma norm: y * (1 + scale)
    scale_embeddings: bool = False        # gemma: embed output * sqrt(hidden)

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads


# Model presets (public architecture configs)
LLAMA3_8B = LlamaConfig(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                        num_layers=32, num_heads=32, num_kv_heads=8)
LLAMA3_70B = LlamaConfig(vocab_size=128256, hidden_size=8192, intermediate_size=28672,
                         num_layers=80, num_heads=64, num_kv_heads=8)
LLAMA2_7B = LlamaConfig(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                        num_layers=32, num_heads=32, num_kv_heads=32, rope_theta=10000.0)
TINY_LLAMA = LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=256,
                         num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256)


def _fit_spec(shape, spec: Tuple, mesh, names) -> Tuple:
    """``spec`` restricted to the mesh axes in ``names``. An entry whose axes
    do not divide its dimension is dropped (that dimension stays replicated):
    the params-init trace runs the model on a 2-row example batch whatever
    the mesh, and a hand-built mesh may lack canonical axes (fsdp_out)."""
    def fit(dim, entry):
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        kept = tuple(a for a in axes if a in names)
        if not kept or dim % int(np.prod([mesh.shape[a] for a in kept])):
            return None
        return kept if isinstance(entry, (tuple, list)) else kept[0]
    return tuple(fit(d, e) for d, e in zip(shape, spec))


def shard_activation(x, spec: Tuple):
    """with_sharding_constraint on the global mesh's axes; a no-op without a
    global mesh."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    mesh = mesh_lib.get_global_mesh()
    if mesh is None:
        return x
    # inside a partial-manual shard_map (e.g. the qgZ int8-wire gradient
    # phase) the manual axes are already local — a constraint naming them
    # would be rejected; keep constraining the still-automatic axes
    region = jax.sharding.get_abstract_mesh()
    manual = set(region.manual_axes)
    spec = _fit_spec(x.shape, spec, mesh, set(mesh.axis_names) - manual)
    if all(e is None for e in spec):
        # an all-None spec would force replication, not act as a no-op
        return x
    # a NamedSharding needs no mesh context; inside a manual region jax wants
    # the context's abstract mesh (outer axes already Manual), not the concrete
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(region if manual else mesh, PartitionSpec(*spec)))


class RMSNorm(nn.Module):
    """RMS norm in fp32 accumulation (reference kernel: csrc rms_norm.cu — here a
    single XLA fusion)."""
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # gemma convention: weights stored as an offset from 1 (zero-init),
    # applied as y * (1 + scale)
    scale_offset: bool = False

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        init = nn.initializers.zeros if self.scale_offset else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        if self.scale_offset:
            scale = scale + 1.0
        return (y * scale).astype(orig_dtype)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN (arXiv:2309.00071) over a rotary part: frequencies whose
    wavelength fits ``original_max_position`` fewer than ``beta_slow`` times
    are divided by ``factor``, those that fit it more than ``beta_fast``
    times are kept, a linear ramp over the dims between blends the two, and
    ``cos`` and ``sin`` are multiplied by ``attention_factor`` (0.1 ln
    ``factor`` + 1 where the config gives none)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


def yarn_inv_freq(rotary_dim: int, theta: float,
                  yarn: YarnScaling) -> np.ndarray:
    """[rotary_dim / 2] float64 inverse frequencies under ``yarn``."""
    pos_freqs = theta ** (np.arange(0, rotary_dim, 2, dtype=np.float64)
                          / rotary_dim)

    def correction_dim(rotations):
        return rotary_dim * np.log(yarn.original_max_position
                                   / (rotations * 2 * np.pi)) \
            / (2 * np.log(theta))
    low = max(np.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(np.ceil(correction_dim(yarn.beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rotary_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    return (1 / (yarn.factor * pos_freqs)) * ramp + (1 / pos_freqs) * (1 - ramp)


@lru_cache(maxsize=32)
def rope_freqs(head_dim: int, max_len: int, theta: float,
               yarn: Optional[YarnScaling] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
    """(cos, sin) [max_len, head_dim / 2] float32. ``head_dim`` is the width
    that rotates: a model with a partial rotary part passes that part's, and
    ``_rope_rows`` lets the dims past it through."""
    # cached: serving policies call this per layer per trace; the cache also
    # keeps the returned ndarrays identical objects so tracers embed one
    # constant instead of num_layers copies
    if yarn is None:
        inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
        scale = 1.0
    else:
        inv = yarn_inv_freq(head_dim, theta, yarn)
        scale = yarn.attention_factor if yarn.attention_factor is not None \
            else 0.1 * np.log(yarn.factor) + 1.0
    t = np.arange(max_len, dtype=np.float64)
    freqs = np.outer(t, inv)
    return (np.cos(freqs) * scale).astype(np.float32), \
        (np.sin(freqs) * scale).astype(np.float32)


def apply_rope(x, cos, sin, positions):
    """x: [B, S, H, D]; positions: [B, S] (reference kernel: apply_rotary_pos_emb.cu)."""
    cos_p = cos[positions][:, :, None, :]   # [B, S, 1, D/2]
    sin_p = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos_p - x2 * sin_p, x2 * cos_p + x1 * sin_p], axis=-1)
    return out.astype(x.dtype)


def softcap_logits(x, cap):
    """tanh soft-capping (gemma2): identity when cap is falsy. The single
    definition shared by training attention, serving paths, and heads."""
    return cap * jnp.tanh(x / cap) if cap else x


def _xla_attention(q, k, v, causal: bool = True, segment_ids=None, window=None,
                   scale=None, softcap=None):
    """Plain attention; XLA fuses softmax chain. q,k,v: [B, S, H, D] / kv
    [B, S, Hkv, D]. ``window`` adds mistral-style sliding-window masking
    (token t attends to (t-window, t]); ``scale`` overrides 1/sqrt(d)
    (gemma2 query_pre_attn_scalar); ``softcap`` tanh-caps the raw logits
    before masking (gemma2 attn_logit_softcapping)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * \
        (scale if scale is not None else 1.0 / np.sqrt(d))
    scores = softcap_logits(scores, softcap)
    sk = k.shape[1]
    if causal or window is not None:
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        kpos = jnp.arange(sk)[None, :]
        mask = qpos >= kpos
        if window is not None:
            mask &= kpos > qpos - window
        scores = jnp.where(mask[None, None], scores, -1e30)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        scores = jnp.where(seg_mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _sharded_flash_attention(q, k, v, causal, window, segment_ids):
    """The flash kernel under the engine's jit. A Mosaic kernel is never
    partitioned automatically (jax refuses to lower one whose sharding context
    spans several devices), so over a mesh each device runs the kernel on its
    own batch rows and heads inside a shard_map, like the ulysses and ring
    backends. The sequence stays whole: this backend has no sequence
    parallelism."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_auto
    kernel = partial(flash_attention_auto, causal=causal, window=window)
    mesh = mesh_lib.get_global_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v, segment_ids=segment_ids)
    region = jax.sharding.get_abstract_mesh()
    manual = set(region.manual_axes)
    auto = set(mesh.axis_names) - manual
    # fitted on k: it has the fewest heads, and q's are a multiple of them
    spec = PartitionSpec(*_fit_spec(
        k.shape, (BATCH_AXES, None, HEADS_AXIS, None), mesh, auto))
    args, in_specs = (q, k, v), (spec, spec, spec)
    if segment_ids is not None:
        args += (jnp.asarray(segment_ids, jnp.int32),)
        in_specs += (PartitionSpec(spec[0], None),)
    return jax.shard_map(
        lambda q, k, v, seg=None: kernel(q, k, v, segment_ids=seg),
        mesh=region if manual else mesh, in_specs=in_specs, out_specs=spec,
        axis_names=frozenset(auto), check_vma=False)(*args)


def _dispatch_attention(backend: str, q, k, v, causal=True, segment_ids=None,
                        mesh=None, window=None):
    if window is not None and backend != "flash":
        # sliding window: explicit mask on the XLA path (the SP backends
        # don't support it; the flash kernel does, with block skipping)
        return _xla_attention(q, k, v, causal, segment_ids, window=window)
    if backend == "xla":
        return _xla_attention(q, k, v, causal, segment_ids)
    if backend == "flash":
        return _sharded_flash_attention(q, k, v, causal, window, segment_ids)
    if backend == "ulysses":
        from deepspeed_tpu.sequence.ulysses import ulysses_attention
        return ulysses_attention(q, k, v, causal=causal,
                                 segment_ids=segment_ids)
    if backend == "ring":
        if segment_ids is not None and jax.default_backend() != "tpu":
            # the jnp ring body has no segment carry; only the flash ring
            # (TPU) masks packed sequences — never silently drop the mask
            raise NotImplementedError(
                "packed-sequence segment_ids with the ring backend need "
                "the flash ring (TPU); on CPU use 'ulysses'/'flash'/'xla'")
        from deepspeed_tpu.sequence.ring import ring_attention
        return ring_attention(q, k, v, causal=causal,
                              segment_ids=segment_ids)
    raise ValueError(f"unknown attention backend '{backend}'")


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        d = cfg.head_dim_
        dense = partial(nn.DenseGeneral, use_bias=cfg.attention_bias,
                        dtype=cfg.dtype, param_dtype=jnp.float32)
        q = dense(features=(cfg.num_heads, d), name="wq")(x)
        k = dense(features=(cfg.num_kv_heads, d), name="wk")(x)
        v = dense(features=(cfg.num_kv_heads, d), name="wv")(x)
        q = shard_activation(q, (BATCH_AXES, SEQ_AXIS, HEADS_AXIS, None))
        k = shard_activation(k, (BATCH_AXES, SEQ_AXIS, HEADS_AXIS, None))
        v = shard_activation(v, (BATCH_AXES, SEQ_AXIS, HEADS_AXIS, None))

        cos, sin = rope_freqs(d, cfg.max_seq_len, cfg.rope_theta)
        cos, sin = jnp.asarray(cos), jnp.asarray(sin)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)

        out = _dispatch_attention(cfg.attention_backend, q, k, v, causal=True,
                                  segment_ids=segment_ids,
                                  window=cfg.sliding_window)
        out = shard_activation(out, (BATCH_AXES, SEQ_AXIS, HEADS_AXIS, None))
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, param_dtype=jnp.float32, name="wo")(out)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=jnp.float32)
        gate = dense(cfg.intermediate_size, name="w_gate")(x)
        up = dense(cfg.intermediate_size, name="w_up")(x)
        if cfg.hidden_act == "silu":
            act = nn.silu
        elif cfg.hidden_act == "gelu_tanh":            # gemma
            act = lambda v: nn.gelu(v, approximate=True)
        else:
            raise ValueError(f"unsupported hidden_act {cfg.hidden_act!r} "
                             "(silu | gelu_tanh)")
        h = act(gate) * up
        h = shard_activation(h, (BATCH_AXES, SEQ_AXIS, HEADS_AXIS))
        return dense(cfg.hidden_size, name="w_down")(h)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        h = x + LlamaAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    scale_offset=cfg.rms_scale_offset, name="attn_norm")(x),
            positions, segment_ids)
        out = h + LlamaMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    scale_offset=cfg.rms_scale_offset, name="mlp_norm")(h))
        return shard_activation(out, (BATCH_AXES, SEQ_AXIS, None))


REMAT_POLICIES = {
    "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
    "everything_saveable": jax.checkpoint_policies.everything_saveable,
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    "dots_with_no_batch_dims_saveable":
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    # saved matmul outputs stream to host RAM instead of staying in HBM
    # (~3.4GB of qkv+gate/up saves per 697M mb=4 step — the r01 OOM dump's
    # dominant allocations); XLA schedules the DMAs around the compute
    "offload_dots_to_host":
        jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            offload_src="device", offload_dst="pinned_host"),
}


class LMHead(nn.Module):
    """Unembedding projection with the kernel exposed as an attribute so the
    chunked-loss path can scan over it (same param path/init as the nn.Dense it
    replaces: ``lm_head/kernel``, fp32 master, lecun-normal)."""
    hidden_size: int
    vocab_size: int
    dtype: Any = jnp.bfloat16

    def setup(self):
        self.kernel = self.param("kernel", nn.initializers.lecun_normal(),
                                 (self.hidden_size, self.vocab_size), jnp.float32)

    def __call__(self, x):
        return jnp.dot(x.astype(self.dtype), self.kernel.astype(self.dtype))


class LlamaModel(nn.Module):
    """Backbone: embed -> N blocks -> final norm. Call with token ids [B, S].
    ``return_hidden=True`` skips the unembed matmul and returns
    ``(hidden [B,S,H], head weights)`` for the chunked-CE loss path (head
    weights are ``embedding [V,H]`` when tied, else ``kernel [H,V]``)."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None,
                 return_hidden=False):
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]),
                                         input_ids.shape)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="embed")
        x = embed(input_ids)
        if cfg.scale_embeddings:          # gemma: normalizer on the embed output
            x = x * jnp.sqrt(jnp.asarray(cfg.hidden_size, jnp.float32)).astype(x.dtype)
        x = shard_activation(x, (BATCH_AXES, SEQ_AXIS, None))

        block_cls = LlamaBlock
        if cfg.remat:
            block_cls = nn.remat(
                LlamaBlock, policy=REMAT_POLICIES[cfg.remat_policy],
                prevent_cse=not cfg.scan_layers, static_argnums=())

        if cfg.scan_layers:
            x, _ = nn.scan(
                lambda mdl, carry, _: (mdl(carry, positions, segment_ids), None),
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block_cls(cfg, name="layers"), x, None)
        else:
            for i in range(cfg.num_layers):
                x = block_cls(cfg, name=f"layer_{i}")(x, positions, segment_ids)

        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    scale_offset=cfg.rms_scale_offset, name="final_norm")(x)
        # head matmul in compute dtype (bf16 on the MXU, fp32 accumulation);
        # downstream softmax casts to fp32 — an fp32 head matmul is ~8x slower
        # device-trace scope: the output head and the loss behind it share
        # one name (the layers' are their modules' names, attn and mlp)
        with jax.named_scope("lm_head_loss"):
            if cfg.tie_embeddings:
                if return_hidden:
                    return x, embed.embedding
                logits = embed.attend(x)
            else:
                head = LMHead(cfg.hidden_size, cfg.vocab_size, cfg.dtype,
                              name="lm_head")
                if return_hidden:
                    return x, head.kernel
                logits = head(x)
            logits = logits.astype(jnp.float32)
            if cfg.logits_soft_cap:
                logits = cfg.logits_soft_cap * \
                    jnp.tanh(logits / cfg.logits_soft_cap)
            return logits


class LlamaForCausalLM(nn.Module):
    """Training entry: batch dict {"input_ids": [B,S]} (+ optional "labels",
    "segment_ids", "positions", "loss_mask") -> mean next-token cross-entropy."""
    cfg: LlamaConfig

    def setup(self):
        self.model = LlamaModel(self.cfg)

    def __call__(self, batch):
        input_ids = batch["input_ids"]
        if self.cfg.loss_chunk_size:
            return self._chunked_loss(batch)
        logits = self.model(input_ids,
                            positions=batch.get("positions"),
                            segment_ids=batch.get("segment_ids"))
        labels = batch.get("labels")
        if labels is None:
            labels = input_ids[:, 1:]
            logits = logits[:, :-1]
            mask = batch.get("loss_mask")
            mask = mask[:, 1:] if mask is not None else jnp.ones_like(labels)
        else:
            mask = batch.get("loss_mask", jnp.ones_like(labels))
        with jax.named_scope("lm_head_loss"):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            mask = mask.astype(jnp.float32)
            return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    def _chunked_loss(self, batch):
        """Same loss as the dense path, via chunked head-matmul + CE fusion.
        Labels/mask are aligned to all S positions (last position masked out in
        the next-token case) so chunk shapes stay static."""
        from deepspeed_tpu.sequence.cross_entropy import chunked_cross_entropy

        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.pad(input_ids[:, 1:], ((0, 0), (0, 1)))
            mask = batch.get("loss_mask")
            mask = mask[:, 1:] if mask is not None else \
                jnp.ones_like(input_ids[:, 1:])
            mask = jnp.pad(mask, ((0, 0), (0, 1)))
        else:
            mask = batch.get("loss_mask", jnp.ones_like(labels))
        hidden, head = self.model(input_ids,
                                  positions=batch.get("positions"),
                                  segment_ids=batch.get("segment_ids"),
                                  return_hidden=True)
        kw = {"embedding": head} if self.cfg.tie_embeddings else {"kernel": head}
        with jax.named_scope("lm_head_loss"):
            return chunked_cross_entropy(
                hidden, labels, mask, chunk_size=self.cfg.loss_chunk_size,
                soft_cap=self.cfg.logits_soft_cap,
                compute_dtype=self.cfg.dtype,
                unroll=self.cfg.loss_chunk_unroll, **kw)

    def logits(self, batch):
        return self.model(batch["input_ids"], positions=batch.get("positions"),
                          segment_ids=batch.get("segment_ids"))


def llama_tensor_rules(path, leaf) -> Optional[PartitionSpec]:
    """Megatron-style TP sharding rules keyed on parameter paths — the AutoTP
    analog (reference module_inject/auto_tp.py:189: column-shard qkv/up, row-shard
    o/down, vocab-shard embeddings).

    Returned specs leave dims free for the fsdp axis to occupy (stage 3 layers on
    a different dim via build_param_shardings).
    """
    name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
    ndim = np.ndim(leaf)
    if "wq/kernel" in name or "wk/kernel" in name or "wv/kernel" in name:
        # [embed, heads, head_dim] -> shard heads
        return PartitionSpec(*([None] * (ndim - 2)), "tensor", None)
    if "wo/kernel" in name:
        # [heads, head_dim, embed] -> shard heads (input-parallel => psum output)
        return PartitionSpec("tensor", *([None] * (ndim - 1)))
    if "w_gate/kernel" in name or "w_up/kernel" in name:
        return PartitionSpec(*([None] * (ndim - 1)), "tensor")
    if "w_down/kernel" in name:
        return PartitionSpec(*([None] * (ndim - 2)), "tensor", None)
    if "embed/embedding" in name:
        return PartitionSpec("tensor", *([None] * (ndim - 1)))
    if "lm_head/kernel" in name:
        return PartitionSpec(*([None] * (ndim - 1)), "tensor")
    return None


def make_llama(cfg: LlamaConfig = TINY_LLAMA):
    return LlamaForCausalLM(cfg)


def random_tokens(batch_size: int, seq_len: int, vocab_size: int = 512,
                  seed: int = 0, gas: Optional[int] = None):
    rng = np.random.default_rng(seed)
    shape = (gas, batch_size, seq_len) if gas else (batch_size, seq_len)
    return {"input_ids": rng.integers(0, vocab_size, size=shape).astype(np.int32)}
