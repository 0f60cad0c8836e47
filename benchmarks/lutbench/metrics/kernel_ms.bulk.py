"""Pallas kernels: device time of the Mosaic kernels per engine call, ms."""


def read(m):
    if m.trace is None or m.trace.kernel_runs == 0:
        return None
    return 1e3 * m.trace.kernel_s / m.trace.kernel_runs
