"""Client generator: 99th percentile of submit time minus due time, ms."""
from measure import percentile


def read(m):
    if m.lateness_s is None or m.lateness_s.size == 0:
        return None
    return percentile(m.lateness_s, 99) * 1e3
