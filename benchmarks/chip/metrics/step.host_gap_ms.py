"""Mean device-idle gap between consecutive executions of the step program
(``runtime/serve.py`` ``Server.decode``) in the traced window: the host's
round trip per tick (logits to the host, argmax, bookkeeping, the next
dispatch). Moves ``tokens_per_s``."""

import numpy as np


def read(c):
    g = c.trace.step_gaps_s()
    return 1e3 * float(np.mean(g)) if g.size else None
