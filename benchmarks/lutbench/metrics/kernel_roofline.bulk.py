"""Pallas kernels: least time one call's required work can take on the
chip (``work.least_time``: bound by HBM bytes for both configurations)
over the kernels' device time per call, %."""
import work


def read(m):
    if m.trace is None or m.trace.kernel_runs == 0 or not m.rows_per_call:
        return None
    least = work.least_time(m.config, m.rows_per_call, m.device_kind)
    return 100.0 * least["seconds"] * m.trace.kernel_runs / m.trace.kernel_s
