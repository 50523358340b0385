"""The scheduler's mean share of batch slots in use at each decode chunk
of the traced round (ContinuousBatchingScheduler.mean_occupancy, already
a share of max_batch), in %."""


def read(r):
    occ = r["occupancy"]
    return None if occ is None else 100.0 * occ
