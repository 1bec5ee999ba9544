"""Mean share of batch rows holding a request (PREFILLING or DECODING) per
tick of the window: host counter of ``runtime/scheduler.py``. Moves
``tokens_per_s``: a row left empty decodes nothing."""

import numpy as np


def read(c):
    ticks = c.ticks()
    if not ticks:
        return None
    return 100.0 * float(np.mean([t.live for t in ticks])) / c.spec.mix["batch"]
