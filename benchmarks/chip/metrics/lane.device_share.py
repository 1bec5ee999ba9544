"""Share of the device's busy time spent in the ops of the prefill lane:
the ops the step program runs under its ``lane`` named scope
(``models/transformer.py`` ``decode_step``: the chunk's attention, MoE and
LM head, which run every tick, chunk or not), over the busy time, averaged
over the chips. Moves ``itl_p95_ms``: every decoding row waits through the
lane's work each tick."""

import scopes


def read(c):
    t = c.trace
    lane, busy = scopes.of(c).scope_s("lane"), t.busy_s()
    return 100.0 * lane / busy if lane > 0 and busy > 0 else None
