"""Unified HF-checkpoint ingestion — the engine_factory analog.

Reference analog: ``deepspeed/inference/v2/engine_factory.py`` (reads the HF
config, picks the arch policy, maps the checkpoint into engine containers).
Here: ``from_hf_checkpoint(hf_config, state_dict)`` dispatches on
``model_type`` to the per-family config mapper + weight converter and returns
``(model, cfg, params)`` ready for training (``deepspeed_tpu.initialize``),
serving (``InferenceEngineV2``), or ZeRO-Inference.
"""

from typing import Any, Dict, Tuple

LLAMA_FAMILY = ("llama", "mistral", "qwen2", "phi3", "gemma")


def _falcon_config(hf: Dict[str, Any]):
    from deepspeed_tpu.models.falcon import FalconConfig
    if hf.get("alibi") or hf.get("parallel_attn", True) is False:
        # falcon-rw variants: ALiBi positions / sequential attn+mlp — a
        # different block than the rotary parallel-attn FalconForCausalLM
        raise ValueError("unsupported falcon variant (alibi or "
                         "non-parallel attention, e.g. falcon-rw); only the "
                         "rotary parallel-attn layout is supported")
    heads = hf["num_attention_heads"]
    if hf.get("new_decoder_architecture"):
        kv = hf.get("num_kv_heads", hf.get("n_head_kv"))
        if kv is None:
            raise ValueError("new_decoder_architecture falcon config is "
                             "missing num_kv_heads / n_head_kv")
    elif hf.get("multi_query", True):
        kv = 1
    else:
        kv = heads
    return FalconConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"], num_heads=heads, num_kv_heads=kv,
        max_seq_len=hf.get("max_position_embeddings", 2048),
        rope_theta=hf.get("rope_theta", 10000.0),
        layer_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        new_decoder_architecture=bool(hf.get("new_decoder_architecture")))


def _opt_config(hf: Dict[str, Any]):
    from deepspeed_tpu.models.opt import OPTConfig
    if hf.get("word_embed_proj_dim", hf["hidden_size"]) != hf["hidden_size"]:
        raise ValueError("unsupported OPT variant: word_embed_proj_dim != "
                         "hidden_size (opt-350m style project_in/out)")
    if hf.get("do_layer_norm_before", True) is False:
        raise ValueError("unsupported OPT variant: post-LN "
                         "(do_layer_norm_before=false)")
    return OPTConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        ffn_dim=hf["ffn_dim"], num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        max_seq_len=hf.get("max_position_embeddings", 2048))


def _bloom_config(hf: Dict[str, Any]):
    from deepspeed_tpu.models.bloom import BloomConfig
    return BloomConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf.get("hidden_size", hf.get("n_embed")),
        num_layers=hf.get("num_hidden_layers", hf.get("n_layer")),
        num_heads=hf.get("num_attention_heads", hf.get("n_head")),
        layer_norm_eps=hf.get("layer_norm_epsilon", 1e-5))


def _gpt2_config(hf: Dict[str, Any]):
    from deepspeed_tpu.models.gpt2 import GPT2Config
    return GPT2Config(
        vocab_size=hf["vocab_size"], hidden_size=hf["n_embd"],
        num_layers=hf["n_layer"], num_heads=hf["n_head"],
        max_seq_len=hf.get("n_positions", 1024),
        layer_norm_eps=hf.get("layer_norm_epsilon", 1e-5))


def _gpt_neox_config(hf: Dict[str, Any]):
    from deepspeed_tpu.models.gpt_neox import GPTNeoXConfig
    return GPTNeoXConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        max_seq_len=hf.get("max_position_embeddings", 2048),
        rotary_pct=hf.get("rotary_pct", 0.25),
        rope_theta=hf.get("rotary_emb_base", 10000.0),
        layer_norm_eps=hf.get("layer_norm_eps", 1e-5),
        parallel_residual=hf.get("use_parallel_residual", True))


def _t5_config(hf: Dict[str, Any]):
    from deepspeed_tpu.models.t5 import T5Config
    ff = hf.get("feed_forward_proj", "relu")
    return T5Config(
        vocab_size=hf["vocab_size"], d_model=hf["d_model"],
        d_kv=hf.get("d_kv", 64), d_ff=hf["d_ff"],
        num_layers=hf["num_layers"],
        num_decoder_layers=hf.get("num_decoder_layers"),
        num_heads=hf["num_heads"],
        relative_attention_num_buckets=hf.get(
            "relative_attention_num_buckets", 32),
        relative_attention_max_distance=hf.get(
            "relative_attention_max_distance", 128),
        layer_norm_eps=hf.get("layer_norm_epsilon", 1e-6),
        gated_act=ff.startswith("gated"),
        tie_word_embeddings=hf.get("tie_word_embeddings", True))


def _llama_family_entry(mt):
    def build():
        from deepspeed_tpu.models.families import (config_from_hf,
                                                   convert_hf_state_dict)
        from deepspeed_tpu.models.llama import LlamaForCausalLM
        return (config_from_hf, LlamaForCausalLM,
                lambda st, cfg: convert_hf_state_dict(st, cfg,
                                                      model_type=mt))
    return build


def _family_entry(mod_name, config_attr, model_attr, convert_attr):
    def build():
        import importlib
        mod = importlib.import_module(f"deepspeed_tpu.models.{mod_name}")
        config_fn = getattr(mod, config_attr) if isinstance(config_attr, str) \
            else config_attr
        return (config_fn, getattr(mod, model_attr),
                getattr(mod, convert_attr))
    return build


# model_type -> thunk building (config_fn, model_ctor, convert_fn); only the
# requested family's module is imported
_REGISTRY = {
    "mixtral": _family_entry("mixtral", "mixtral_config_from_hf",
                             "MixtralForCausalLM", "convert_hf_mixtral"),
    "qwen2_moe": _family_entry("qwen2_moe", "qwen2_moe_config_from_hf",
                               "Qwen2MoEForCausalLM", "convert_hf_qwen2_moe"),
    "joyai_llm_flash": _family_entry("joyai_llm_flash",
                                     "joyai_flash_config_from_hf",
                                     "JoyAIFlashForCausalLM",
                                     "convert_hf_joyai_flash"),
    "laguna": _family_entry("laguna", "laguna_config_from_hf",
                            "LagunaForCausalLM", "convert_hf_laguna"),
    "xing4_0": _family_entry("xing4", "xing4_config_from_hf",
                             "Xing4ForCausalLM", "convert_hf_xing4"),
    "mimo_v2": _family_entry("mimo_v2", "mimo_v2_config_from_hf",
                             "MiMoV2ForCausalLM", "convert_hf_mimo_v2"),
    "granitemoehybrid": _family_entry("granite_hybrid",
                                      "granite_hybrid_config_from_hf",
                                      "GraniteHybridForCausalLM",
                                      "convert_hf_granite_hybrid"),
    "nemotron_h": _family_entry("nemotron_h", "nemotron_h_config_from_hf",
                                "NemotronHForCausalLM",
                                "convert_hf_nemotron_h"),
    "lfm2_moe": _family_entry("lfm2_moe", "lfm2_moe_config_from_hf",
                              "Lfm2MoeForCausalLM", "convert_hf_lfm2_moe"),
    "falcon": _family_entry("falcon", _falcon_config, "FalconForCausalLM",
                            "convert_hf_falcon"),
    "opt": _family_entry("opt", _opt_config, "OPTForCausalLM",
                         "convert_hf_opt"),
    "bloom": _family_entry("bloom", _bloom_config, "BloomForCausalLM",
                           "convert_hf_bloom"),
    "gpt2": _family_entry("gpt2", _gpt2_config, "GPT2ForCausalLM",
                          "convert_hf_gpt2"),
    "gpt_neox": _family_entry("gpt_neox", _gpt_neox_config,
                              "GPTNeoXForCausalLM", "convert_hf_gpt_neox"),
    "t5": _family_entry("t5", _t5_config, "T5ForConditionalGeneration",
                        "convert_hf_t5"),
    "gemma2": _family_entry("gemma2", "gemma2_config_from_hf",
                            "Gemma2ForCausalLM", "convert_hf_gemma2"),
    **{mt: _llama_family_entry(mt) for mt in LLAMA_FAMILY},
}


def supported_model_types() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def from_hf_checkpoint(hf_config: Dict[str, Any], state_dict=None):
    """(hf config dict, optional state dict) -> (model, cfg, params).
    ``params`` is None when no state dict is given (config-only use).
    Raises on unknown ``model_type`` with the supported list."""
    mt = hf_config.get("model_type")
    if mt not in _REGISTRY:
        raise ValueError(
            f"unsupported model_type {mt!r}; supported: "
            f"{', '.join(sorted(_REGISTRY))}")
    config_fn, model_ctor, convert_fn = _REGISTRY[mt]()
    cfg = config_fn(hf_config)
    model = model_ctor(cfg)
    params = convert_fn(state_dict, cfg) if state_dict is not None else None
    return model, cfg, params
