"""Dense GQA decoders served by ``ray_tpu.models.llama`` (Llama, Mistral):
the published Hugging Face keys, the program's settings under ``system``."""

from __future__ import annotations


def model_config(config: dict):
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config["torch_dtype"]],
        remat=config["system"].get("remat", "none"),
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import llama

    return llama.init_params(model_cfg, key)
