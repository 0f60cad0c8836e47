"""Device: share of the traced window with no device operation, %."""


def read(m):
    if m.trace is None:
        return None
    return 100.0 * m.trace.idle_share
