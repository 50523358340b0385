"""Kimi K2 (moonshotai/Kimi-K2-Instruct, config.json,
https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json):
the DeepSeek-V3 block at a trillion parameters. 61 layers, d_model 7168,
64 heads of multi-head latent attention (q_lora_rank 1536, kv_lora_rank
512, q/k heads 128 + 64 rotary, v heads 128), one dense first layer of
18432, then 60 MoE layers of 384 routed experts of 2048 (8 a token) and
one shared expert, a sigmoid router with a correction bias (noaux_tc, one
group, gates renormalised, routed scaling 2.827), vocab 163840, untied
embeddings, rms_norm_eps 1e-6; about 1.03T parameters, 32B active. The
published YaRN rope scaling (factor 32 over 4096 positions) is not
modelled: plain RoPE at theta 50000. The paper-table arch for pod-scale
MoE training: requires FSDP + expert parallelism + Adafactor to approach
a 16 GiB/chip pod; serving waits on a latent KV cache."""

from repro.configs.registry import CellSettings
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b", family="moe",
    n_layers=61, d_model=7168, n_heads=64, n_kv_heads=64, d_ff=18432,
    vocab_size=163840, rope_theta=5e4, norm_eps=1e-6,
    kv_lora_rank=512, q_lora_rank=1536, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128,
    n_experts=384, experts_per_token=8, d_expert=2048, n_shared_experts=1,
    router="sigmoid", routed_scaling=2.827, first_dense_layers=1,
    capacity_factor=1.25,
)

SMOKE = ModelConfig(
    name="kimi-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96,
    vocab_size=211, rope_theta=5e4,
    kv_lora_rank=16, q_lora_rank=24, qk_nope_dim=8, qk_rope_dim=8,
    v_head_dim=8,
    n_experts=8, experts_per_token=2, d_expert=32, n_shared_experts=1,
    router="sigmoid", routed_scaling=2.827, first_dense_layers=1,
    capacity_factor=1.25,
)

SETTINGS = {
    "default": CellSettings(rules="fsdp_tp_sp", param_dtype="bfloat16",
                            optimizer="adafactor"),
    "train_4k": CellSettings(microbatches=16, rules="fsdp_tp_sp",
                             param_dtype="bfloat16", optimizer="adafactor",
                             accum_dtype="bfloat16", q_chunk=2048),
    "prefill_32k": CellSettings(rules="fsdp_tp_sp",
                                param_dtype="float8_e4m3fn",
                                cache_dtype="int8", q_chunk=512),
    "decode_32k": CellSettings(rules="fsdp_tp_sp",
                               param_dtype="float8_e4m3fn",
                               cache_dtype="int8"),
}
