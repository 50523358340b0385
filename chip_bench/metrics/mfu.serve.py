"""Model FLOPs of every prefill and decode token the traced round needed
(chip_bench/flops.py, from the requests' shapes), over the traced
window x chips x the chip's published bf16 peak."""


def read(r):
    t = r["trace"]
    peak = r["peaks"]["bf16_flops"] * r["chips"]
    return 100.0 * r["model_flops"] / (t.window_s * peak)
