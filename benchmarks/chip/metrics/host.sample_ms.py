"""Host time per tick inside the scheduler's ``sched.sample`` span
(``runtime/scheduler.py``): the decode logits copied to the host, then
finiteness, argmax and each row's token, and a final chunk's first token.
The device waits through it for the next step. Moves ``tokens_per_s``."""

import scopes


def read(c):
    return scopes.per_tick_ms(c.trace, "sched.sample")
