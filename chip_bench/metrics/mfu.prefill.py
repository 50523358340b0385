"""Model FLOPs of the prefill positions the traced round needed (every
prompt position not served from the prefix cache, logits at the last;
chip_bench/flops.py) over the traced window x chips x the chip's
published bf16 peak: the whole window's share of the peak that prefill
work fills, which bounds what a faster span kernel can show."""


def read(r):
    t = r["trace"]
    peak = r["peaks"]["bf16_flops"] * r["chips"]
    return 100.0 * r["prefill_flops"] / (t.window_s * peak)
