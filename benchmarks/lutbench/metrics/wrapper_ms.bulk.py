"""Engine: device time per engine call in device operations other than
the Mosaic kernels (what XLA runs around the kernel: copies, pads and
layout changes of the codes), ms.  Busy and kernel time are sums of
whole-nanosecond intervals, so their difference is rounded to the
nanosecond."""


def read(m):
    if m.trace is None or m.trace.kernel_runs == 0:
        return None
    other_ns = round((m.trace.busy_s - m.trace.kernel_s) * 1e9)
    return 1e-6 * other_ns / m.trace.kernel_runs
