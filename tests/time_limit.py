"""Per-test wall-clock limit.

``tests/conftest.py`` wraps every test in ``time_limit()``, so a test
that hangs fails by name, with every thread's stack dumped to its
captured stderr, instead of running the whole suite into its outer
timeout.
"""
import contextlib
import faulthandler
import signal
import time

import pytest

# About a sixth of the tier-1 suite's 1,470 s budget, and over four times
# the slowest default-lane test (55 s alone on a CPU).
TEST_TIME_LIMIT_S = 240


@contextlib.contextmanager
def time_limit(seconds: float = TEST_TIME_LIMIT_S):
    """Fail the enclosed block once it has run ``seconds`` of wall time.

    Uses SIGALRM, so it must be entered on the main thread, which is
    where pytest (and xdist's ``main_thread_only`` workers) run tests.
    An enclosing limit is re-armed on exit with what is left of it.
    """
    def expire(signum, frame):
        faulthandler.dump_traceback(all_threads=True)
        pytest.fail(f"test exceeded its {seconds} s time limit")

    previous = signal.signal(signal.SIGALRM, expire)
    outer_left, _ = signal.setitimer(signal.ITIMER_REAL, seconds)
    start = time.monotonic()
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        if outer_left:
            signal.setitimer(signal.ITIMER_REAL, max(
                outer_left - (time.monotonic() - start), 1e-3))
