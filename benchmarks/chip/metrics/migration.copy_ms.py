"""Device time of the NI-Balancer's migration slice copies in the window,
in ms: the ops under the ``migration`` named scope
(``runtime/migration_driver.py`` ``_copy_row_slice``), averaged over the
chips; 0 when no slice ran. Moves ``itl_p95_ms``: the copies run between
decode steps."""

import scopes


def read(c):
    s = scopes.of(c)
    if s.scope_s("decode") <= 0:      # a step program without named scopes
        return None
    return 1e3 * s.scope_s("migration")
