"""Whole engine step: least time of one call's required work
(``work.least_time``) times the calls that ran in the traced window,
over the window, %: the step's share of the chip's peak."""
import work


def read(m):
    if m.trace is None or m.trace.kernel_runs == 0 or not m.rows_per_call:
        return None
    least = work.least_time(m.config, m.rows_per_call, m.device_kind)
    return 100.0 * least["seconds"] * m.trace.kernel_runs / m.trace.window_s
