"""The training flash-attention kernels' least time for the work the
traced steps' attention needed (``flops.flash_train_work``: three times
the causal forward's FLOPs, the rematerialised forwards not counted;
q, k, v, o and their gradients read or written once) over the summed
device time of the forward and both backward kernels. The bound is
compute: at 14/2 heads of width 64 the bytes take under a fifth of the
least time."""

import numpy as np

from chip_bench.flops import flash_train_work
from chip_bench.peaks import least_time_s

# the Pallas kernels' names, which their HLO instructions carry
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(r):
    t = sum(r["trace"].kernel_s(k) for k in KERNELS)
    if not t:
        return None
    act = np.dtype(r["conf"]["precision"]["activations"]).itemsize
    f, b = flash_train_work(r["dims"], r["seq"], r["sequences"], act)
    return 100.0 * least_time_s(f, b, r["peaks"])[0] / t
