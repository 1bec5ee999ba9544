"""Median host-clock time of the window's ticks that carry a prefill-lane
chunk (``Server.decode`` with a chunk operand). Moves ``itl_p95_ms``: every
decoding row waits through such a tick, the slowest kind there is."""

import numpy as np


def read(c):
    d = [t.t1 - t.t0 for t in c.ticks() if t.chunk is not None]
    return 1e3 * float(np.median(d)) if d else None
