"""The paged span (chunked prefill) kernel's least time for the work
the traced round needed (each chunk's queries, and the keys and values
of its context once per layer; chip_bench/flops.py) over the kernel's
summed device time. Each chunk is held to its own bound: bytes for a
prompt's first chunks, compute once about 1,000 tokens of context lie
behind the chunk (ridge: 240 FLOP per byte)."""

from chip_bench.peaks import least_time_s

# the Pallas wrapper the kernel's HLO instruction is named after
KERNEL = "paged_decode_span_attention"


def read(r):
    t = r["trace"].kernel_s(KERNEL)
    if not t:
        return None
    least = sum(least_time_s(f, b, r["peaks"])[0] for f, b in r["span_work"])
    return 100.0 * least / t
