"""Closed-loop bulk scoring through the ``make_network_fn`` engine.

Traffic file keys: ``kind: "bulk"``, ``rows_per_call``, ``in_flight``
(calls dispatched and not yet fetched), ``batch_pool`` (distinct input
batches drawn from the seed; call ``i`` scores batch ``i mod
batch_pool``), ``checked_calls`` (calls, drawn from the seed, whose
outputs are compared with the reference after the window).

Each call moves its int32 codes from the host to the device, runs the
engine built from the cold-loaded artifact, and fetches the output
codes to the host.  ``rows_per_s`` is the rows of every call fetched
in the window over the window's length, which ends at the first fetch
past ``seconds``.
"""
from __future__ import annotations

import collections
import dataclasses
import random
import time
from typing import Any, Dict, List

import numpy as np

import reference
import tables
from measure import WINDOW, Measurements, quiet_gc, span


@dataclasses.dataclass
class State:
    cell: Any
    layers: List[dict]
    engine: Any
    batches: List[np.ndarray]
    check_seed: int
    kept: Dict[int, np.ndarray] = None


def setup(cell, seed: int, seconds: float, workdir: str, part) -> State:
    import jax

    with part("import_program"):
        from repro.artifact import load_artifact
        from repro.kernels.lut_gather import ops

    cfg, tr = cell.config, cell.traffic
    with part("tables"):
        layers = tables.draw_tables(cfg, seed)
    with part("artifact_save"):
        path = tables.save(cfg, layers, workdir)
    rng = tables.host_rng(seed, 2)
    with part("inputs"):
        batches = [tables.input_codes(cfg, tr["rows_per_call"], rng)
                   for _ in range(tr["batch_pool"])]
    with part("load_compile_warm"):
        engine = ops.make_network_fn(load_artifact(path, unpack_int4=False))
        for x in batches[:tr["in_flight"]]:
            np.asarray(engine(jax.device_put(x)))
    return State(cell=cell, layers=layers, engine=engine, batches=batches,
                 check_seed=seed)


def window(st: State, seconds: float, tracer) -> Measurements:
    import jax

    tr = st.cell.traffic
    engine, batches, depth = st.engine, st.batches, tr["in_flight"]
    inflight: collections.deque = collections.deque()
    # a reservoir sample, drawn from the seed, of the calls fetched in
    # the window: ``checked_calls`` slots of (call, output)
    rnd = random.Random(st.check_seed)
    slots, n_slots = [], tr["checked_calls"]
    quiet_gc()
    tracer.start()
    t0 = time.monotonic()
    end = t0 + seconds
    i = done = 0
    with span(WINDOW):
        while True:
            if len(inflight) == depth:
                k, out = inflight.popleft()
                with span("lutbench.fetch"):
                    got = np.asarray(out)
                if len(slots) < n_slots:
                    slots.append((k, got))
                else:
                    r = rnd.randrange(done + 1)
                    if r < n_slots:
                        slots[r] = (k, got)
                done += 1
                t_last = time.monotonic()
                if t_last >= end:
                    break
            with span("lutbench.device_put"):
                x = jax.device_put(batches[i % len(batches)])
            with span("lutbench.dispatch"):
                inflight.append((i, engine(x)))
            i += 1
    while inflight:                  # past the window: not counted
        np.asarray(inflight.popleft()[1])
    tracer.stop()
    st.kept = dict(slots)
    return Measurements(
        config=st.cell.config, t_start=t0, window_s=t_last - t0, attempted=i,
        failed=0, rows=done * tr["rows_per_call"],
        rows_per_call=tr["rows_per_call"])


def close(st: State) -> None:
    st.engine = None


def end_to_end(m: Measurements) -> dict:
    return {"rows_per_s": m.rows / m.window_s}


def check(st: State, m: Measurements, lower_precision: bool = False) -> dict:
    """The checked calls' outputs against the reference on their input
    batches; with ``lower_precision`` the control's tables stand in for
    the program's answers."""
    layers = st.layers
    n_pool = len(st.batches)
    ids = sorted({k % n_pool for k in st.kept})
    want = {b: reference.forward(layers, st.batches[b]) for b in ids}
    if lower_precision:
        low = reference.lower_precision(layers)
        control = {b: reference.forward(low, st.batches[b]) for b in ids}
    bad = 0
    for k, got in st.kept.items():
        if lower_precision:
            got = control[k % n_pool]
        bad += reference.mismatched_rows(got, want[k % n_pool])
    return {"mismatched_rows": (bad, 0)}
