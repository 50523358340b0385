"""The grouped expert GEMM kernels' least time for the traced steps'
routed work (``mla_moe_work.moe_gemm_train_work``: the rows the program
counted as routed to the held experts, ``moe_held_rows``, through the
three expert matrices, forward, dx and dw; rows in and out once a GEMM,
the held weights and their gradients once a layer and step) over the
device time of ``gmm`` (forward and dx) and ``gmm_dw``."""

import numpy as np

from chip_bench.mla_moe_work import moe_gemm_train_work
from chip_bench.peaks import least_time_s

KERNELS = ("gmm", "gmm_dw")


def read(r):
    t = sum(r["trace"].kernel_s(k) for k in KERNELS)
    rows = r["counters_window"].get("moe_held_rows")
    if not t or not rows:
        return None
    prec = r["conf"]["precision"]
    f, b = moe_gemm_train_work(
        r["dims"], rows, r["steps"],
        np.dtype(prec["activations"]).itemsize,
        np.dtype(prec["weights"]).itemsize)
    return 100.0 * least_time_s(f, b, r["peaks"])[0] / t
