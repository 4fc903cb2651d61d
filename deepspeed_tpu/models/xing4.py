"""Xing4.0 causal LM (``model_type: xing4_0``, 29B-A4B): JoyAI-LLM-Flash's
layers (``models/joyai_llm_flash.py``: latent attention with a low-rank
query, sigmoid-routed experts chosen with a correction bias, one shared
expert, leading dense layers) with YaRN on the rotary part, round every one
of which ``hc_mult`` residual streams are mixed by manifold-constrained
hyper-connections (``inference/v2/hyper_connection.py`` has the equations).

Reference analog: none in ``deepspeed/inference/v2/model_implementations``.
The state between layers is ``[..., hc_mult, hidden]``: the embedding row
copied ``hc_mult`` times, each layer two sublayers (attention; the dense MLP
or the experts), each with its own pre-norm inside it and its own mixing
parameters, the streams summed before the final norm and the untied head.
Attention, routing and the expert layer are JoyAI's modules, not copies.

The multi-token-prediction module (``num_nextn_predict_layers``) adds nothing
to the model's logits and is not built or loaded.
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import joyai_llm_flash as jm
from deepspeed_tpu.models.llama import LMHead, RMSNorm, YarnScaling
from deepspeed_tpu.inference.v2 import hyper_connection as hcx


@dataclasses.dataclass(frozen=True)
class Xing4Config(jm.JoyAIFlashConfig):
    """JoyAI's fields at Xing4.0's published sizes, and the mixing's."""
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    q_lora_rank: int = 768
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    first_k_dense_replace: int = 2
    max_seq_len: int = 262144
    rope_theta: float = 10000.0
    rope_yarn: Optional[YarnScaling] = YarnScaling(64.0, 4096, 32.0, 1.0, 1.0)
    yarn_mscale_all_dim: float = 1.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0

    @property
    def hc(self) -> hcx.HyperConnection:
        return hcx.HyperConnection(
            self.hc_mult, self.hc_sinkhorn_iters, self.hc_eps,
            self.hc_clamp_min, self.hc_clamp_max, self.rms_norm_eps)


TINY_XING4 = Xing4Config(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_layers=5, num_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    n_routed_experts=16, num_experts_per_tok=4, max_seq_len=512,
    rope_yarn=YarnScaling(8.0, 64, 32.0, 1.0, 1.0))


class _Mixing(nn.Module):
    """One sublayer's mixing parameters."""
    cfg: Xing4Config

    @nn.compact
    def __call__(self):
        hc, width = self.cfg.hc, self.cfg.hidden_size
        return {name: self.param(name, lambda key, name=name:
                                 hcx.init_params(key, hc, width)[name])
                for name in ("phi", "alpha", "bias")}


class Xing4Block(nn.Module):
    cfg: Xing4Config
    layer: int

    @nn.compact
    def __call__(self, x, positions):
        """x: [B, S, n, C]."""
        cfg = self.cfg
        hc = cfg.hc
        b, s = x.shape[:2]
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)

        def around(name, sublayer, x):
            flat = x.reshape((b * s,) + x.shape[2:])
            u, mix = hcx.pre_mix(flat, _Mixing(cfg, name=name)(), hc)
            y = sublayer(u.reshape(b, s, -1))
            return hcx.post_mix(flat, y.reshape(b * s, -1), mix,
                                hc).reshape(x.shape)

        x = around("hc_attn", lambda u: jm.JoyAIFlashAttention(
            cfg, name="attn")(norm("attn_norm")(u), positions), x)
        if cfg.is_dense(self.layer):
            ffn = lambda u: jm._SwiGLU(cfg, cfg.intermediate_size,
                                       name="mlp")(norm("mlp_norm")(u))
        else:
            ffn = lambda u: jm.JoyAIFlashMoE(cfg, name="moe")(
                norm("mlp_norm")(u))
        return around("hc_mlp", ffn, x)


class Xing4ForCausalLM(nn.Module):
    """batch {"input_ids": [B, S]} -> next-token loss; ``logits(batch)`` the
    float32 logits."""
    cfg: Xing4Config

    @nn.compact
    def _backbone(self, input_ids):
        cfg = self.cfg
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]),
                                     input_ids.shape)
        h = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="embed")(input_ids)
        x = hcx.expand(h, cfg.hc)
        for i in range(cfg.num_layers):
            x = Xing4Block(cfg, i, name=f"layer_{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(
            hcx.collapse(x))
        return LMHead(cfg.hidden_size, cfg.vocab_size, cfg.dtype,
                      name="lm_head")(x).astype(jnp.float32)

    @property
    def config(self):
        return self.cfg

    def __call__(self, batch):
        input_ids = batch["input_ids"]
        logp = jax.nn.log_softmax(self._backbone(input_ids)[:, :-1], axis=-1)
        ll = jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)
        return -jnp.mean(ll)

    def logits(self, batch):
        return self._backbone(batch["input_ids"])


# ---------------------------------------------------------------------------
# HF interop (DeepSeek-V3's names; the mixing's are assumed)
# ---------------------------------------------------------------------------
def xing4_config_from_hf(hf: dict,
                         max_seq_len: Optional[int] = None) -> Xing4Config:
    """A ``Xing4Config`` from the published ``config.json`` keys: JoyAI's
    (``latent_moe_fields``, with what that family refuses) and the ``hc_*``
    ones. The file has no ``rope_interleave``: the pairs ``(2j, 2j+1)``
    rotate, DeepSeek-V3's default."""
    return Xing4Config(
        **jm.latent_moe_fields(hf, max_seq_len, family="xing4_0"),
        hc_mult=int(hf["hc_mult"]),
        hc_sinkhorn_iters=int(hf["hc_sinkhorn_iters"]),
        hc_eps=float(hf["hc_eps"]),
        hc_clamp_min=float(hf["mhc_h_res_clamp_min"]),
        hc_clamp_max=float(hf["mhc_h_res_clamp_max"]))


#: (a layer's mixing in our tree, the checkpoint's prefix under the layer):
#: assumed names, the config's own prefixes
_MIXING_NAMES = (("hc_attn", "hc_attn."), ("hc_mlp", "hc_ffn."))

def convert_hf_xing4(hf_state, cfg: Xing4Config):
    """``convert_hf_joyai_flash``'s tree with each layer's two mixings
    (``model.layers.<i>.hc_attn.`` / ``hc_ffn.``: ``phi.weight`` [n^2 + 2 n,
    n C], ``bias``, ``alpha``)."""
    from deepspeed_tpu.models.families import hf_get
    tree = jm.convert_hf_joyai_flash(hf_state, cfg)
    for i in range(cfg.num_layers):
        for ours, theirs in _MIXING_NAMES:
            p = f"model.layers.{i}.{theirs}"
            tree[f"layer_{i}"][ours] = {
                "phi": np.asarray(hf_get(hf_state, p + "phi.weight")).T,
                "bias": hf_get(hf_state, p + "bias"),
                "alpha": hf_get(hf_state, p + "alpha")}
    return tree


def export_hf_xing4(params, cfg: Xing4Config) -> dict:
    """The inverse of ``convert_hf_xing4``."""
    out = jm.export_hf_joyai_flash(params, cfg)
    for i in range(cfg.num_layers):
        for ours, theirs in _MIXING_NAMES:
            p, mixing = f"model.layers.{i}.{theirs}", params[f"layer_{i}"][ours]
            out[p + "phi.weight"] = np.asarray(mixing["phi"], np.float32).T
            out[p + "bias"] = np.asarray(mixing["bias"], np.float32)
            out[p + "alpha"] = np.asarray(mixing["alpha"], np.float32)
    return out
