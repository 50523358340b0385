"""The training flash-attention kernels' least time for the work the
traced steps' latent attention needed (``mla_moe_work.mla_flash_train_work``:
three times the causal forward's FLOPs, scores at the q/k width and the
weighted sum at the v width, the rematerialised forwards not counted;
q, k, v, o and their gradients read or written once) over the summed
device time of the forward and both backward kernels."""

import numpy as np

from chip_bench.mla_moe_work import mla_flash_train_work
from chip_bench.peaks import least_time_s

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(r):
    t = sum(r["trace"].kernel_s(k) for k in KERNELS)
    n = r["dims"]
    if not t or "dqk" not in n:
        return None
    act = np.dtype(r["conf"]["precision"]["activations"]).itemsize
    f, b = mla_flash_train_work(n, r["seq"], r["sequences"], act)
    return 100.0 * least_time_s(f, b, r["peaks"])[0] / t
