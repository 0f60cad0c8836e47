"""The per-test time limit (tests/time_limit.py) fails a stuck test by
name within its limit, and leaves the enclosing test's own limit
armed."""
import signal
import time

import pytest

from time_limit import time_limit


def test_time_limit_fails_a_stuck_block():
    start = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match="exceeded its 1 s time limit"):
        with time_limit(1):
            time.sleep(5)
    assert time.monotonic() - start < 2
    # the autouse limit around this test is armed again
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0
