"""Share of the traced window in which no operation ran on the chip
(averaged over the chips): 1 - union of op intervals / window. Moves
``tokens_per_s``."""


def read(c):
    t = c.trace
    return 100.0 * (1.0 - t.busy_s() / t.window_s) if t.window_s > 0 else None
