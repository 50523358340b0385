"""Operations and bytes the kernels of a DeepSeek-V3-block training step
need, by ``flops.py``'s rules (two per multiply-add; nothing padded,
re-fetched or recomputed; each tensor read or written once), from the
dimensions ``reference/deepseek_v3.dims`` gives."""

from __future__ import annotations

from typing import Dict, Tuple

from chip_bench.flops import span_keys_sum


def mla_flash_train_work(n: Dict[str, int], seq: int, sequences: int,
                         act_bytes: int = 4) -> Tuple[int, int]:
    """(FLOPs, bytes) of the training flash-attention kernels (forward
    and both backward) over ``sequences`` causal sequences of ``seq``
    tokens, over every layer, at MLA's widths: scores at the q/k width
    ``dqk``, the weighted sum at the v width ``dv``, ``H`` heads. FLOPs:
    three times the forward's. Bytes: q, k (at ``dqk``), v, o (at
    ``dv``) and their gradients, each once."""
    per_key = 2 * n["H"] * (n["dqk"] + n["dv"])
    flops = 3 * per_key * span_keys_sum(0, seq) * n["L"] * sequences
    per_layer = 2 * 2 * n["H"] * (n["dqk"] + n["dv"]) * seq * act_bytes
    return flops, per_layer * n["L"] * sequences


def moe_gemm_train_work(n: Dict[str, int], held_rows: float, steps: int,
                        act_bytes: int = 4,
                        weight_bytes: int = 4) -> Tuple[float, float]:
    """(FLOPs, bytes) of the grouped expert GEMMs of ``steps`` training
    steps whose MoE layers routed ``held_rows`` assignments (summed over
    layers) to the held experts. FLOPs: the three expert matrices,
    2 D Fe each per row, three times (forward, dx, dw). Bytes: each row
    in and out of each of the nine GEMMs ((D + Fe) a row each), and the
    held weights read by the forward and dx GEMMs and their gradients
    written, once per MoE layer and step."""
    d, fe = n["D"], n["Fe"]
    flops = 3 * 3 * 2 * d * fe * held_rows
    rows = 9 * (d + fe) * act_bytes * held_rows
    weights = 9 * n["Eh"] * d * fe * weight_bytes * n["Lm"] * steps
    return flops, rows + weights
