"""The program's configuration of a DeepSeek-V3-family ``config.json``
(Moonlight, Kimi K2): MLA, a leading dense layer, then MoE layers with
shared experts and a sigmoid router. ``n_routed_experts`` is the number
of experts held here, ``router_experts`` (default: all of them) how many
the router chooses from, ``first_held_expert`` the first one held."""

from __future__ import annotations

from typing import Any, Dict

from repro.models.config import ModelConfig


# what the program's sigmoid router and MLP compute
EXPECTED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "moe_layer_freq": 1, "hidden_act": "silu"}


def model_config(conf: Dict[str, Any]) -> ModelConfig:
    for k, v in EXPECTED.items():
        if conf.get(k, v) != v:
            raise ValueError(f"{conf['name']}: {k}={conf[k]!r} is not "
                             f"modelled (only {v!r})")
    held = conf["n_routed_experts"]
    h = conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=h, n_kv_heads=h, d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"],
        kv_lora_rank=conf["kv_lora_rank"], q_lora_rank=conf["q_lora_rank"],
        qk_nope_dim=conf["qk_nope_head_dim"],
        qk_rope_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        n_experts=conf.get("router_experts", held),
        experts_per_token=conf["num_experts_per_tok"],
        d_expert=conf["moe_intermediate_size"],
        n_shared_experts=conf["n_shared_experts"],
        router=conf["scoring_func"],
        routed_scaling=conf["routed_scaling_factor"],
        experts_held=held, first_expert=conf.get("first_held_expert", 0),
        first_dense_layers=conf["first_k_dense_replace"])
