"""Forward and backward FLOPs of the traced steps' tokens, as the
configuration's reference module counts them (``train_flops``: three
times the forward, recomputation not counted; chip_bench/flops.py), over
the traced window x chips x the chip's published bf16 peak."""


def read(r):
    t = r["trace"]
    peak = r["peaks"]["bf16_flops"] * r["chips"]
    return 100.0 * r["model_flops"] / (t.window_s * peak)
