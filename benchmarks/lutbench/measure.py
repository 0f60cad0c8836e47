"""What a run measured, as the per-layer readers see it, and the host
spans the benchmark writes into the profiler's trace."""
from __future__ import annotations

import dataclasses
import gc
import math
from typing import Any, List, Optional, Sequence

import numpy as np

# Names of the benchmark's own host spans (``span``): the trace reducer
# labels the device's idle gaps with them.
WINDOW = "lutbench.window"
# A request the generator submits this late marks a stall of the host.
STALL_S = 0.02


@dataclasses.dataclass
class Measurements:
    config: dict
    t_start: float                      # window start, time.monotonic()
    window_s: float                     # length of the measured window
    attempted: int                      # requests (stream) or calls (bulk)
    failed: int                         # failed, refused or never answered
    rows: int = 0                       # rows completed in the window
    latency_s: Optional[np.ndarray] = None   # due -> done, of each request
    lateness_s: Optional[np.ndarray] = None  # due -> submit
    flushes: Optional[List[Any]] = None      # FlushRecords of the window
    rows_per_call: int = 0
    trace: Optional[Any] = None         # trace_reduce.Summary of a traced run
    device_kind: str = ""
    # (monotonic time, longest lateness in s) of each run of requests the
    # generator submitted over STALL_S late: the host stalled
    stalls: Optional[List[Any]] = None


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile: the smallest value with at least
    ``q`` percent of the values at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        return math.nan
    return float(v[max(0, math.ceil(q / 100.0 * v.size) - 1)])


def stall_events(due: np.ndarray, lateness: np.ndarray,
                 gap_s: float = 0.5) -> List[tuple]:
    """Group requests submitted over ``STALL_S`` late into events, a new
    one after ``gap_s`` without one: ``(due time of the first, longest
    lateness)`` each."""
    late = np.flatnonzero(np.nan_to_num(lateness) > STALL_S)
    events: List[tuple] = []
    for k in late:
        if events and due[k] - events[-1][2] <= gap_s:
            t, worst, _ = events[-1]
            events[-1] = (t, max(worst, lateness[k]), due[k])
        else:
            events.append((due[k], lateness[k], due[k]))
    return [(float(t), float(w)) for t, w, _ in events]


def quiet_gc() -> None:
    """Collect, then freeze what set-up left, so the collector's full
    passes inside the window do not scan it."""
    gc.collect()
    gc.freeze()
