"""The program's configuration of a Qwen2-family ``config.json``."""

from __future__ import annotations

from typing import Any, Dict

from repro.models.config import ModelConfig


def model_config(conf: Dict[str, Any]) -> ModelConfig:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf.get("head_dim") or d // h, qkv_bias=True,
        rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"])
