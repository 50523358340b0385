"""The paged decode kernel's least time for the work the traced round
needed (queries, and the keys and values of each live context once per
layer; chip_bench/flops.py) over the kernel's summed device time. The
bound is bytes: about 3.5 FLOP per byte at Qwen2's 14/2 heads."""

from chip_bench.peaks import least_time_s

# the Pallas wrapper the kernel's HLO instruction is named after
KERNEL = "paged_decode_attention"


def read(r):
    t = r["trace"].kernel_s(KERNEL)
    if not t:
        return None
    least = sum(least_time_s(f, b, r["peaks"])[0] for f, b in r["decode_work"])
    return 100.0 * least / t
