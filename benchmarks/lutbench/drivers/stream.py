"""Open-loop stream of single-row requests into ``ModelRegistry.submit``.

Traffic file keys: ``kind: "stream"``, ``rate_per_s`` (offered rate,
fixed in the file), ``microbatch`` and ``deadline_ms`` (the registry's
batcher), ``row_pool`` (distinct input rows drawn from the seed; each
request takes one, chosen from the seed), ``warm_requests``.

Arrivals: ``round(rate_per_s * seconds)`` due times, uniform over the
window and sorted: a Poisson process with its count fixed, so every
seed offers the same work in another order.  The generator wakes at
the next due time and submits every request whose due time has passed.
Each request's latency runs from its due time to its completion; its
lateness is submit time minus due time.  A request that fails, is
refused or never completes is a miss, with the latency of the longest
wait, ``seconds + WAIT_PAST_CLOSE_S``.
"""
from __future__ import annotations

import bisect
import dataclasses
import gc
import time
from typing import Any, List

import numpy as np

import reference
import tables
from measure import WINDOW, Measurements, quiet_gc, span, stall_events

START_DELAY_S = 0.05    # first due time after the window opens
WAIT_PAST_CLOSE_S = 60.0  # longest wait for answers after the window
# The window holds every handle for the check after it.  Frozen every
# FREEZE_EVERY requests, they stay out of the collector's full passes,
# which would otherwise scan all of them and stall the process.
FREEZE_EVERY = 4096


@dataclasses.dataclass
class State:
    cell: Any
    layers: List[dict]
    registry: Any
    entry: Any
    model_id: str
    pool: np.ndarray          # (row_pool, n_in) input codes
    offsets: np.ndarray       # due times from the window's start, sorted
    row_of: np.ndarray        # pool row of each request
    req_rows: list = None     # each request's row, a view into the pool
    handles: list = None
    flush0: int = 0
    t0: float = 0.0           # the last window's start (monotonic)


def schedule(rate: float, seconds: float, pool: int,
             rng: np.random.Generator):
    """Due times (from the window's start, sorted) and pool rows of
    ``round(rate * seconds)`` requests."""
    n = int(round(rate * seconds))
    offsets = START_DELAY_S + np.sort(rng.uniform(0.0, seconds, n))
    return offsets, rng.integers(0, pool, n)


def setup(cell, seed: int, seconds: float, workdir: str, part) -> State:
    with part("import_program"):
        import repro.artifact  # noqa: F401
        from repro.launch.registry import ModelRegistry

    cfg, tr = cell.config, cell.traffic
    with part("tables"):
        layers = tables.draw_tables(cfg, seed)
    with part("artifact_save"):
        path = tables.save(cfg, layers, workdir)
    rng = tables.host_rng(seed, 1)
    with part("inputs"):
        pool = tables.input_codes(cfg, tr["row_pool"], rng)
        offsets, row_of = schedule(tr["rate_per_s"], seconds,
                                   tr["row_pool"], rng)
    registry = ModelRegistry(tr["microbatch"], tr["deadline_ms"] * 1e-3)
    with part("load_compile_warm"):
        entry = registry.register(cfg["name"], path)
        warm = [registry.submit(cfg["name"], pool[i % len(pool)])
                for i in range(tr["warm_requests"])]
        for h in warm:
            h.result(timeout=WAIT_PAST_CLOSE_S)
    st = State(cell=cell, layers=layers, registry=registry, entry=entry,
               model_id=cfg["name"], pool=pool, offsets=offsets,
               row_of=row_of)
    with part("inputs"):
        plan(st)
    return st


def plan(st: State) -> None:
    """Each request's input row, ready before the window opens."""
    rows = list(st.pool)
    st.req_rows = [rows[j] for j in st.row_of]


def window(st: State, seconds: float, tracer) -> Measurements:
    submit, model_id, req_rows = st.registry.submit, st.model_id, st.req_rows
    n = len(req_rows)
    handles: list = [None] * n
    st.flush0 = len(st.entry.batcher.flushes)
    quiet_gc()
    tracer.start()
    t0 = st.t0 = time.monotonic()
    due = (t0 + st.offsets).tolist()
    i, next_freeze = 0, FREEZE_EVERY
    with span(WINDOW):
        while i < n:
            now = time.monotonic()
            if due[i] > now:
                time.sleep(due[i] - now)
                continue
            j = bisect.bisect_right(due, now, i)
            with span("lutbench.release"):
                for k in range(i, j):
                    try:
                        handles[k] = submit(model_id, req_rows[k])
                    except Exception:       # refused: a miss
                        handles[k] = None
            i = j
            if i >= next_freeze:
                gc.freeze()
                next_freeze += FREEZE_EVERY
    t_close = due[-1] if n else t0
    deadline = time.monotonic() + WAIT_PAST_CLOSE_S
    with span("lutbench.result_wait"):
        for h in handles:
            if h is not None:
                try:
                    h.result(timeout=max(0.0, deadline - time.monotonic()))
                except (TimeoutError, RuntimeError):
                    pass                    # counted as a miss below
    tracer.stop()
    st.handles = handles
    flushes = st.entry.batcher.flushes[st.flush0:]

    due_a = np.asarray(due)
    miss_s = seconds + WAIT_PAST_CLOSE_S
    latency = np.full(n, miss_s)
    lateness = np.full(n, np.nan)
    failed = 0
    for k, h in enumerate(handles):
        if h is None or not h.done or h.failed:
            failed += 1
            continue
        latency[k] = h.t_done - due_a[k]
        lateness[k] = h.t_submit - due_a[k]
    return Measurements(
        config=st.cell.config, t_start=t0, window_s=t_close - t0,
        attempted=n,
        failed=failed, rows=n - failed, latency_s=latency,
        lateness_s=lateness[np.isfinite(lateness)], flushes=flushes,
        stalls=stall_events(due_a, lateness))


def close(st: State) -> None:
    st.registry.close()


def end_to_end(m: Measurements) -> dict:
    from measure import percentile

    return {"latency_p50_ms": percentile(m.latency_s, 50) * 1e3,
            "latency_p95_ms": percentile(m.latency_s, 95) * 1e3}


def check(st: State, m: Measurements, lower_precision: bool = False) -> dict:
    """Every answered request against the reference on its pool row;
    with ``lower_precision`` the control's tables stand in for the
    program's answers."""
    layers = st.layers
    want = reference.forward(layers, st.pool)
    answered = [k for k, h in enumerate(st.handles)
                if h is not None and h.done and not h.failed]
    if lower_precision:
        control = reference.forward(reference.lower_precision(layers),
                                    st.pool)
        got = control[st.row_of[answered]]
    else:
        got = (np.stack([st.handles[k].result() for k in answered])
               if answered else np.zeros((0, want.shape[1]), np.int32))
    return {"mismatched_rows": (reference.mismatched_rows(
                got, want[st.row_of[answered]]), 0),
            "unanswered": (m.attempted - len(answered), 0)}
