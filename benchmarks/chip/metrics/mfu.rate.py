"""``mfu`` over the time spent inside ticks only: below the knee the open
loop's window also holds waits for arrivals. Moves ``itl_p95_ms``."""


def read(c):
    return c.mfu(busy_only=True)
