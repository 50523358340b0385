"""Share of the traced training steps in which no operation ran on the
device (1 - union of device-op intervals / window), mean over chips."""


def read(r):
    t = r["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)
