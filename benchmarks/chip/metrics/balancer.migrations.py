"""Expert migrations the NI-Balancer committed during the window
(``Server.migrations``, ``core/ni_balancer.py`` and
``runtime/migration_driver.py``). Moves ``itl_p95_ms``: a migration's
slice copies ride the decode ticks."""


def read(c):
    return c.run.migrations
