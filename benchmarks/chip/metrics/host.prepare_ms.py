"""Host time per tick spent getting the next step onto the device: the
spans ``sched.admit`` (faults, admission, headroom), ``sched.chunk`` (the
lane's operand), ``server.prepare`` (operands, pages, layout),
``server.migrate`` (migration commits and slice copies) and
``server.dispatch`` (the step's launch), ``runtime/scheduler.py`` and
``runtime/serve.py``. Moves ``tokens_per_s``."""

import scopes

SPANS = ("sched.admit", "sched.chunk", "server.prepare", "server.migrate",
         "server.dispatch")


def read(c):
    return scopes.per_tick_ms(c.trace, *SPANS)
