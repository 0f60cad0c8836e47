"""LUT inference engine benchmark: fused vs per-layer, packed vs int32
vs int4-in-kernel, grid-tiled vs double-buffered, single-device vs
sharded, plus deadline-flush serving tail latency.

Tracks the perf trajectory of the lut_gather serving path across PRs.
Six execution strategies over identical synthesised networks:

  seed        the seed's broadcast-gather layout over int32 tables, as
              the plain jnp oracle chain (kernels/lut_gather/ref.py);
              the Pallas kernels no longer carry that layout
  per-layer   per-layer pallas_call, packed uint8 tables, lane-gather
  fused       whole network in ONE pallas_call, packed uint8 tables,
              matmul routing, VMEM activation scratch
  fused-int4  the fused engine on int4 NIBBLE-PACKED slabs — two codes
              per byte resident in VMEM, shift/mask unpack per lookup
              (halves table residency; the VMEM ledger below tracks it)
  pipelined   the fused engine with double-buffered batch tiles: codes
              in/out stay in HBM and the kernel overlaps tile i+1's DMA
              with tile i's compute (compared against a serial-tile
              grid baseline at one fixed multi-tile size — see below)
  sharded     the fused engine shard_map'ed over the batch axis of all
              visible devices, tables replicated

Each config also records the VMEM ledger that gates fusion
(``vmem_bytes_fused_uint8`` / ``_int4``, the per-tile claim
``vmem_tile_bytes_grid`` / ``_pipelined``, and
``table_residency_ratio_int4`` — contractually <= 0.55 for
4-bit-code adder networks) plus the ``tune_block_b`` sweep winners;

plus a ``serving`` section: a real Poisson request stream through the
threaded deadline-flush microbatcher (launch/batching.py), reporting
p50/p95/p99 request latency, the straggler queueing-delay p99, and
whether p99 lands under the deadline SLO (deadline + 2 kernel times);

plus a ``fleet`` section (schema v5): the multi-replica serving ledger
— open-loop throughput behind the least-outstanding router at {1, 2, 4}
replicas (threads stand in for hosts on this box, so the series tracks
ROUTING overhead, not parallel speedup), the router's submit-side
overhead p50/p99, the two-phase coordinated swap's prepare/commit
window and per-replica blackout, and the replica-crash drill — both
drills contractually complete with zero dropped requests
(tests/test_bench_schema.py pins this, tests/test_fleet.py pins the
mechanism);

plus a ``scheduler`` section (schema v8): the SLO-tiered scoreboard
scheduler (launch/scheduler.py) under a mixed interactive/batch
Poisson stream at 2x one replica's calibrated steal-inclusive
capacity, through tier-aware fleets of {1, 2, 4} replicas — per-tier
p50/p99, interactive deadline attainment, typed-shed rate, work-steal
counters, and the zero-silent-drop contract (every non-served request
is a typed ``DeadlineUnmeetable``; tests/test_bench_schema.py pins it
at every replica count);

plus an ``rpc_fleet`` section (schema v9): what the cross-process
socket transport costs over threads-as-hosts — per-request wire
overhead p50/p99 (closed-loop, microbatch 1, thread fleet vs a real
worker process behind the length-prefixed RPC), streamed slab-transfer
throughput with the worker's SHA-256 admission re-hash on the clock,
and the heartbeat prober's detection latency for a SIGKILLed worker
(contracts: zero drops, percentile ordering, real bytes moved, death
detected);

plus a ``segmented`` section (schema v6): the over-budget regime — a
deeper/wider net whose table slabs want ~3x the fused VMEM budget, so
``ops.plan_segments`` cuts it into the fewest fused segments that fit
(adopting int4-packed slabs when that saves a segment) and chains the
inter-segment activation codes through HBM.  Records the plan
(segments, bounds, cut widths, per-segment VMEM), the HBM bytes each
cut moves (``2 * B * width * 4``: one store + one load of int32
codes), and an interleaved timing pair against the per-layer fallback
— ``speedup_segmented_vs_per_layer`` is contractually > 1.5x (the
whole point of segmenting instead of falling off the fusion cliff);

plus an ``artifact`` section: the compile-once ledger — how long
``build_lut_model`` takes from scratch (train + synthesise) vs
COLD-LOADING the same network from a content-addressed repro/artifact
directory (the deployment path; tracked speedup must stay >= 10x), the
PACKED cold load (``unpack_int4=False``: int4 slabs stay
two-codes-per-byte from disk into the kernel, ``cold_load_packed_ms`` /
``table_bytes_loaded_packed``), and a hot-swap drill through
launch/registry under live Poisson load recording the routing blackout
and the dropped-request count (contractually zero).

On this CPU container all kernels run in Pallas interpret mode and the
"devices" are virtual host devices (the module forces
``--xla_force_host_platform_device_count=4`` before jax initialises),
so the numbers are a proxy (documented in the JSON as
backend/interpret); the relative ordering is what is tracked.  (The
double-buffer win is understated here: interpret mode executes DMAs
synchronously, so overlap shows up only as the removal of per-grid-step
block slicing.)  ``python -m benchmarks.run --json`` (or ``python -m
benchmarks.lut_infer_bench --json``) writes ``BENCH_lut_infer.json``
at the repo root in a stable schema (pinned by
tests/test_bench_schema.py):

    {"bench": "lut_infer", "schema_version": 4, "backend": ...,
     "configs": [{name, batch, widths, ..., fused_packed_ms,
                  fused_int4_ms, fused_serial_tile_ms,
                  fused_pipelined_ms (the last two: an interleaved
                  min-of-iters pair, BOTH engines at the same fixed
                  multi-tile size pipeline_pair_block_b =
                  max(256, batch // 4) — independent of the
                  block_b_tuned* sweep winners, which are recorded
                  separately),
                  speedup_int4_vs_uint8, speedup_pipelined_vs_serial,
                  vmem_bytes_fused_uint8, vmem_bytes_fused_int4,
                  vmem_ratio_int4_vs_uint8, table_residency_ratio_int4,
                  vmem_tile_bytes_grid, vmem_tile_bytes_pipelined,
                  block_b_tuned, block_b_tuned_pipelined,
                  sharded_devices, sharded_fused_ms, ...}],
     "serving": {microbatch, deadline_ms, rate, requests, shards,
                 p50_ms, p95_ms, p99_ms, straggler_p99_ms,
                 deadline_slo_ms, p99_under_deadline, ...},
     "artifact": {build_from_scratch_ms, save_ms, cold_load_ms,
                  cold_load_packed_ms, table_bytes_loaded_packed,
                  speedup_cold_load_vs_build, artifact_slab_bytes,
                  swap_requests, swap_dropped, swap_blackout_ms,
                  swap_warm_ms, ...}}

``tokens_per_sec_fused`` is an intentional alias of
``samples_per_sec_fused`` (one classified sample = one token of
serving work) so cross-bench dashboards can read a uniform key.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import tempfile
import threading
import time

# virtual host devices for the sharded series — a no-op when jax is
# already initialised (benchmarks/run.py sets the flag first)
from repro.xla_env import ensure_host_devices

ensure_host_devices(4)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import paired_timed, print_table, timed
from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.kernels.lut_gather import ops as lg_ops, ref as lg_ref
from repro.launch.batching import (MicroBatcher, latency_percentiles_ms,
                                   replay_open_loop)
from repro.parallel.sharding import serving_mesh

JSON_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_lut_infer.json"

# deep nets are where fusion pays: one kernel replaces L x (tiles)
# pallas_calls and all inter-layer HBM round-trips
CONFIGS = [
    ("jsc-m-add2", dict(in_features=16, widths=(64, 32, 32, 32, 5),
                        bits=2, fan_in=3, degree=1, adder_width=2)),
    ("jsc-wide-f6", dict(in_features=16, widths=(32, 16, 5),
                         bits=2, fan_in=6, degree=1, adder_width=2)),
    ("logicnets-deep", dict(in_features=16, widths=(64, 32, 32, 5),
                            bits=2, fan_in=3, degree=1, adder_width=1)),
]


def _ref_chain(tables, codes):
    for t in tables:
        codes = lg_ref.lut_layer(codes, t.conn, t.sub_table, t.add_table,
                                 t.in_bits, t.sub_bits)
    return codes


def _bench_config(name: str, kw: dict, batch: int, iters: int):
    spec = LD.ModelSpec(name=name, **kw)
    model = LD.init_model(jax.random.key(0), spec)
    packed = LS.synthesise(model, spec, pack=True)
    legacy = LS.synthesise(model, spec, pack=False)
    int4 = LS.pack_tables_int4(packed)
    codes = jax.random.randint(
        jax.random.key(1), (batch, spec.in_features), 0,
        2 ** spec.layer_specs()[0].in_quant.bits).astype(jnp.int32)

    # bit-exactness guard: a benchmark of a wrong kernel is worthless
    want = _ref_chain(legacy, codes)
    seed_fn = jax.jit(lambda c: _ref_chain(legacy, c))
    per_layer_fn = jax.jit(lambda c: lg_ops.lut_network(packed, c))
    per_layer_i32_fn = jax.jit(lambda c: lg_ops.lut_network(legacy, c))
    fused_fn = lg_ops.make_network_fn(packed, fused=True, block_b=batch)
    int4_fn = lg_ops.make_network_fn(int4, fused=True, block_b=batch)
    for f in (seed_fn, per_layer_fn, fused_fn, int4_fn):
        assert np.array_equal(np.asarray(f(codes)), np.asarray(want)), name

    t_seed = timed(seed_fn, codes, iters=iters)
    t_pl = timed(per_layer_fn, codes, iters=iters)
    t_pl_i32 = timed(per_layer_i32_fn, codes, iters=iters)
    t_fused = timed(fused_fn, codes, iters=iters)
    t_int4 = timed(int4_fn, codes, iters=iters)

    # block_b autotune sweeps (the serving-entry "auto" path), then the
    # serial-TILE vs double-buffered comparison in the MULTI-TILE
    # regime the pipeline exists for (4 tiles: batch // 4) — measured
    # as an INTERLEAVED min-of-iters pair so machine-load drift hits
    # both engines equally (this box is a noisy shared CPU; a 1-tile
    # comparison would measure nothing but that noise)
    cand = tuple(sorted({256, 1024, 2048, batch}))
    bb_serial, _ = lg_ops.tune_block_b(packed, batch=batch,
                                       candidates=cand, iters=2)
    bb_pipe, _ = lg_ops.tune_block_b(packed, batch=batch,
                                     candidates=cand, iters=2,
                                     pipeline=True)
    bb_pair = max(256, batch // 4)
    serial_tile_fn = lg_ops.make_network_fn(packed, fused=True,
                                            block_b=bb_pair)
    pipe_fn = lg_ops.make_network_fn(packed, fused=True, block_b=bb_pair,
                                     pipeline=True)
    assert np.array_equal(np.asarray(pipe_fn(codes)),
                          np.asarray(want)), f"{name} pipelined"
    t_serial_tile, t_pipe = paired_timed(serial_tile_fn, pipe_fn, codes,
                                         iters=max(iters, 10))

    # sharded fused: batch over all visible devices, tables replicated
    n_dev = jax.device_count()
    sharded_fn = lg_ops.make_network_fn(packed, fused=True, block_b=batch,
                                        mesh=serving_mesh(n_dev))
    assert np.array_equal(np.asarray(sharded_fn(codes)),
                          np.asarray(want)), f"{name} sharded"
    t_sharded = timed(sharded_fn, codes, iters=iters)

    # the VMEM ledger that gates fusion eligibility
    n_in = spec.in_features
    vmem_u8 = lg_ops.fused_vmem_bytes(packed, batch, n_in)
    vmem_i4 = lg_ops.fused_vmem_bytes(int4, batch, n_in)
    slab_u8 = sum(t.table_bytes for t in packed)
    slab_i4 = sum(t.table_bytes for t in int4)

    sps_fused = batch / t_fused
    return {
        "name": name,
        "batch": batch,
        "widths": list(kw["widths"]),
        "fan_in": kw["fan_in"],
        "bits": kw["bits"],
        "adder_width": kw["adder_width"],
        "table_bytes_int32": LS.network_table_bytes(legacy),
        "table_bytes_packed": LS.network_table_bytes(packed),
        "table_bytes_int4": LS.network_table_bytes(int4),
        "table_residency_ratio_int4": round(slab_i4 / slab_u8, 3),
        "vmem_bytes_fused_uint8": vmem_u8,
        "vmem_bytes_fused_int4": vmem_i4,
        "vmem_ratio_int4_vs_uint8": round(vmem_i4 / vmem_u8, 3),
        "vmem_tile_bytes_grid": lg_ops.fused_tile_bytes(
            packed, bb_pair, n_in),
        "vmem_tile_bytes_pipelined": lg_ops.fused_tile_bytes(
            packed, bb_pair, n_in, pipeline=True),
        "pipeline_pair_block_b": bb_pair,
        "seed_per_layer_int32_ms": round(t_seed * 1e3, 3),
        "per_layer_int32_flat_ms": round(t_pl_i32 * 1e3, 3),
        "per_layer_packed_ms": round(t_pl * 1e3, 3),
        "fused_packed_ms": round(t_fused * 1e3, 3),
        "fused_int4_ms": round(t_int4 * 1e3, 3),
        "fused_serial_tile_ms": round(t_serial_tile * 1e3, 3),
        "fused_pipelined_ms": round(t_pipe * 1e3, 3),
        "block_b_tuned": bb_serial,
        "block_b_tuned_pipelined": bb_pipe,
        "samples_per_sec_seed": round(batch / t_seed),
        "samples_per_sec_fused": round(sps_fused),
        "samples_per_sec_int4": round(batch / t_int4),
        "tokens_per_sec_fused": round(sps_fused),
        "speedup_fused_vs_seed": round(t_seed / t_fused, 2),
        "speedup_packed_vs_int32": round(t_pl_i32 / t_pl, 2),
        "speedup_int4_vs_uint8": round(t_fused / t_int4, 2),
        "speedup_pipelined_vs_serial": round(t_serial_tile / t_pipe, 2),
        "sharded_devices": n_dev,
        "sharded_fused_ms": round(t_sharded * 1e3, 3),
        "samples_per_sec_sharded": round(batch / t_sharded),
        "speedup_sharded_vs_fused": round(t_fused / t_sharded, 2),
    }


# deliberately OVER the 12 MiB fused-VMEM budget (~3x): six 512-wide
# fan-in-6 adder layers put ~34 MB of table slabs on the wish list, so
# the cost model MUST cut the net into fused segments — the series this
# section tracks is "segmented beats the per-layer fallback"
SEG_CONFIG = ("deeper-wider-3x",
              dict(in_features=16,
                   widths=(512, 512, 512, 512, 512, 512, 5),
                   bits=2, fan_in=6, degree=1, adder_width=2))


def _bench_segmented(fast: bool):
    """Cost-model-driven segmented execution on an over-budget net:
    ``plan_segments`` splits the layer list into the fewest fused
    pallas_calls whose slabs fit VMEM, chaining activation codes
    through HBM between segments.  Timed as an interleaved pair against
    the per-layer fallback (what an over-budget net ran as before the
    planner existed); the oracle is the jnp reference chain."""
    name, kw = SEG_CONFIG
    batch = 1024 if fast else 4096
    iters = 2 if fast else 3
    spec = LD.ModelSpec(name=name, **kw)
    model = LD.init_model(jax.random.key(2), spec)
    packed = LS.synthesise(model, spec, pack=True)
    codes = jax.random.randint(
        jax.random.key(3), (batch, spec.in_features), 0,
        2 ** spec.layer_specs()[0].in_quant.bits).astype(jnp.int32)

    n_in = spec.in_features
    budget = lg_ops.FUSED_VMEM_BUDGET_BYTES
    vmem_u8 = lg_ops.fused_vmem_bytes(packed, 1024, n_in)

    # the plan-driven serving entry: fused=None -> plan_segments picks
    # the execution shape (and may adopt int4 packing when it saves a
    # segment); the same call an in-budget net takes to ONE segment
    seg_fn = lg_ops.make_network_fn(packed, n_in0=n_in)
    plan = seg_fn.execution_plan
    assert plan.mode == "segmented" and plan.n_segments >= 2, \
        plan.describe()
    per_layer_fn = jax.jit(lambda c: lg_ops.lut_network(packed, c))

    # bit-exactness guard: a benchmark of a wrong kernel is worthless
    want = codes
    for t in packed:
        want = LS.lut_layer_forward(t, want)
    assert np.array_equal(np.asarray(seg_fn(codes)),
                          np.asarray(want)), name
    assert np.array_equal(np.asarray(per_layer_fn(codes)),
                          np.asarray(want)), f"{name} per-layer"

    t_pl, t_seg = paired_timed(per_layer_fn, seg_fn, codes, iters=iters)

    hbm_per_cut = list(plan.hbm_bytes_per_cut(batch))
    return {
        "name": name,
        "batch": batch,
        "widths": list(kw["widths"]),
        "fan_in": kw["fan_in"],
        "mode": plan.mode,
        "segments": plan.n_segments,
        "segment_bounds": [list(b) for b in plan.bounds],
        "block_b": list(plan.block_b),
        "pack_int4": plan.pack_int4,
        "pipeline": plan.pipeline,
        "cut_widths": list(plan.cut_widths),
        "hbm_bytes_per_cut": hbm_per_cut,
        "hbm_bytes_per_pass": sum(hbm_per_cut),
        "vmem_bytes_fused_uint8": vmem_u8,
        "vmem_bytes_per_segment": list(plan.vmem_bytes),
        "budget_bytes": budget,
        "over_budget_ratio": round(vmem_u8 / budget, 2),
        "segmented_ms": round(t_seg * 1e3, 3),
        "per_layer_ms": round(t_pl * 1e3, 3),
        "samples_per_sec_segmented": round(batch / t_seg),
        "speedup_segmented_vs_per_layer": round(t_pl / t_seg, 2),
    }


def _bench_serving(fast: bool):
    """Deadline-flush serving tail latency: a real Poisson stream
    through the threaded microbatcher into the (sharded when multiple
    devices are visible) fused engine.  The offered rate sits below the
    interpret-mode service capacity so the p99 measures the FLUSH
    policy, not unbounded overload queueing."""
    microbatch = 256
    deadline_ms = 2.0
    rate = 5_000.0 if fast else 10_000.0
    requests = 512 if fast else 2048
    n_dev = jax.device_count()

    spec = LD.ModelSpec(name="serve", in_features=16,
                        widths=(64, 32, 32, 32, 5), bits=2, fan_in=3,
                        degree=1, adder_width=2)
    tables = LS.synthesise(LD.init_model(jax.random.key(0), spec),
                           spec, pack=True)
    mesh = serving_mesh(n_dev) if n_dev > 1 else None
    fn = lg_ops.make_network_fn(tables, fused=True, block_b=microbatch,
                                mesh=mesh)
    jax.block_until_ready(fn(jnp.zeros((microbatch, 16), jnp.int32)))

    def engine(batch_np):
        return np.asarray(jax.block_until_ready(fn(jnp.asarray(batch_np))))

    rows = np.asarray(jax.random.randint(
        jax.random.key(2), (requests, 16), 0, 4), np.int32)
    with MicroBatcher(engine, microbatch, deadline_ms / 1e3,
                      n_features=16) as mb:
        handles = replay_open_loop(mb, rows, rate, seed=0)

    # failed handles carry time-to-fault, not service latency — keep
    # them out of the tail the dashboard tracks (explicit here because
    # this number is the one cross-PR latency series)
    p50, p95, p99 = latency_percentiles_ms(handles, include_failed=False)
    kernel_ms = [f.kernel_s * 1e3 for f in mb.flushes]
    straggler_ms = [f.waited_s * 1e3 for f in mb.flushes]
    # SLO: a request waits at most the flush deadline plus (worst case)
    # the in-flight batch's kernel and its own batch's kernel
    slo_ms = deadline_ms + 2 * float(np.percentile(kernel_ms, 99))
    return {
        "microbatch": microbatch,
        "deadline_ms": deadline_ms,
        "rate": rate,
        "requests": requests,
        "shards": n_dev if mesh is not None else 1,
        "p50_ms": round(p50, 3),
        "p95_ms": round(p95, 3),
        "p99_ms": round(p99, 3),
        "straggler_p99_ms": round(
            float(np.percentile(straggler_ms, 99)), 3),
        "deadline_slo_ms": round(slo_ms, 3),
        "p99_under_deadline": bool(p99 <= slo_ms),
        "mean_flush_fill": round(
            float(np.mean([f.fill for f in mb.flushes])), 1),
        "deadline_flushes": int(
            sum(f.deadline_hit for f in mb.flushes)),
    }


def _bench_artifact(fast: bool):
    """Compile-once ledger + hot-swap drill.

    build_from_scratch_ms is what every process start PAID before the
    artifact store existed (train + synthesise via the launcher's
    canonical assembly); cold_load_ms is the deployment path (hash-
    verified memmap load, no trainer).  The swap drill routes a live
    Poisson stream through launch/registry.ModelRegistry and replaces
    the serving tables mid-stream: dropped must be 0 and the blackout
    is the routing-lock hold, not an engine warm-up."""
    from repro.artifact import load_artifact, save_artifact
    from repro.launch.batching import replay_open_loop
    from repro.launch.registry import ModelRegistry
    from repro.launch.serve import build_lut_model

    train_steps = 40 if fast else 150
    t0 = time.perf_counter()
    spec, tables, _ = build_lut_model(train_steps)
    build_s = time.perf_counter() - t0

    tmp = tempfile.mkdtemp(prefix="lut-bench-artifacts-")
    t0 = time.perf_counter()
    path = save_artifact(tmp, tables, spec=spec,
                         provenance={"train_steps": train_steps})
    save_s = time.perf_counter() - t0
    loads = []
    for _ in range(5):
        t0 = time.perf_counter()
        art = load_artifact(path)          # verify=True: hash-checked
        loads.append(time.perf_counter() - t0)
    cold_load_s = float(np.median(loads))
    loads_packed = []
    for _ in range(5):
        t0 = time.perf_counter()
        art_packed = load_artifact(path, unpack_int4=False)
        loads_packed.append(time.perf_counter() - t0)
    cold_load_packed_s = float(np.median(loads_packed))

    # a benchmark of a wrong loader is worthless
    codes = jax.random.randint(jax.random.key(3),
                               (256, spec.in_features), 0, 4, jnp.int32)
    want = np.asarray(lg_ops.lut_network_fused(tables, codes, block_b=256))
    got = np.asarray(lg_ops.lut_network_fused(art.tables, codes,
                                              block_b=256))
    assert np.array_equal(want, got), "artifact round-trip not bit-exact"
    got_packed = np.asarray(lg_ops.lut_network_fused(
        art_packed.tables, codes, block_b=256))
    assert np.array_equal(want, got_packed), \
        "packed artifact round-trip not bit-exact"

    # hot-swap drill: stream long enough that the new engine's
    # trace+compile warm-up ENDS while requests still arrive
    requests = 256 if fast else 1024
    rate = 500.0 if fast else 1000.0
    swap_tables = LS.synthesise(
        LD.init_model(jax.random.key(1), spec), spec)
    rows = np.asarray(jax.random.randint(
        jax.random.key(4), (requests, spec.in_features), 0, 4), np.int32)
    with ModelRegistry(microbatch=64, deadline_s=2e-3) as reg:
        reg.register("m", art)
        handles: list = []
        feeder = threading.Thread(target=lambda: handles.extend(
            replay_open_loop(reg.client("m"), rows, rate, seed=0)))
        t_span = time.monotonic()
        feeder.start()
        time.sleep(0.25 * requests / rate)
        rep = reg.swap("m", swap_tables)
        feeder.join()
        span = time.monotonic() - t_span
    shutil.rmtree(tmp, ignore_errors=True)
    # two DISTINCT contract violations: a dropped request never
    # completed at all; a failed one completed with an engine error
    dropped = requests - sum(1 for h in handles if h.done)
    failed = sum(1 for h in handles if h.failed)

    return {
        "train_steps": train_steps,
        "build_from_scratch_ms": round(build_s * 1e3, 1),
        "save_ms": round(save_s * 1e3, 2),
        "cold_load_ms": round(cold_load_s * 1e3, 2),
        "cold_load_packed_ms": round(cold_load_packed_s * 1e3, 2),
        "speedup_cold_load_vs_build": round(build_s / cold_load_s, 1),
        "artifact_slab_bytes": int(art.manifest["total_slab_bytes"]),
        "table_bytes_packed": LS.network_table_bytes(tables),
        "table_bytes_loaded_packed": LS.network_table_bytes(
            art_packed.tables),
        "swap_requests": requests,
        "swap_rate": rate,
        "swap_dropped": int(dropped),
        "swap_failed": int(failed),
        "swap_blackout_ms": round(rep.blackout_s * 1e3, 4),
        "swap_warm_ms": round(rep.warm_s * 1e3, 1),
        "swap_drained_on_old": int(rep.drained_requests),
        "swap_throughput_req_s": round(len(handles) / span),
    }


def _bench_fleet(fast: bool):
    """Multi-replica fleet ledger (schema v5): per-replica-count
    throughput {1, 2, 4}, the router's own submit-side overhead
    (p50/p99 of the time spent picking a replica + enqueueing, the cost
    the fleet adds over a bare batcher), the two-phase coordinated-swap
    blackout, and the crash drill's zero-drop count.

    On this box the "replicas" are threads sharing one CPU, so the
    replica-count series tracks ROUTING overhead and contract
    compliance, not parallel speedup — real scaling needs real hosts
    (the ROADMAP's recorded residual).  The two hardware-independent
    contracts (pinned by tests/test_bench_schema.py): the crash drill
    and the swap drill both complete with ZERO dropped requests."""
    from repro.artifact import save_artifact
    from repro.launch.fleet import LutFleet
    from repro.launch.serve import build_lut_model

    microbatch = 64
    deadline_s = 2e-3
    requests = 384 if fast else 1024
    rate = 1e9                 # open loop saturated at submitter speed
    train_steps = 40 if fast else 150

    spec, tables_v1, _ = build_lut_model(train_steps, seed=0)
    _, tables_v2, _ = build_lut_model(train_steps, seed=1)
    tmp = tempfile.mkdtemp(prefix="lut-bench-fleet-")
    p1 = save_artifact(tmp, tables_v1, name="fleet-v1", spec=spec)
    p2 = save_artifact(tmp, tables_v2, name="fleet-v2", spec=spec)
    rows = np.asarray(jax.random.randint(
        jax.random.key(5), (requests, spec.in_features), 0, 4), np.int32)

    out = {
        "microbatch": microbatch,
        "deadline_ms": deadline_s * 1e3,
        "requests": requests,
        "replica_counts": [1, 2, 4],
    }
    route_us: list = []
    for n in (1, 2, 4):
        with LutFleet(n, microbatch, deadline_s) as fleet:
            fleet.distribute_artifact(p1, "m")
            t0 = time.monotonic()
            handles = replay_open_loop(fleet.client("m"), rows, rate,
                                       seed=0)
            span = time.monotonic() - t0
        out[f"throughput_req_s_r{n}"] = round(len(handles) / span)
        if n == 4:
            route_us = [h.route_s * 1e6 for h in handles]
    out["scaling_r4_vs_r1"] = round(
        out["throughput_req_s_r4"] / out["throughput_req_s_r1"], 2)
    out["route_overhead_p50_us"] = round(
        float(np.percentile(route_us, 50)), 2)
    out["route_overhead_p99_us"] = round(
        float(np.percentile(route_us, 99)), 2)

    # coordinated swap drill under live load: prepare fleet-wide
    # off-path, commit cuts every replica in one tight loop
    with LutFleet(2, microbatch, deadline_s) as fleet:
        fleet.distribute_artifact(p1, "m")
        handles = []
        feeder = threading.Thread(target=lambda: handles.extend(
            replay_open_loop(fleet.client("m"),
                             np.tile(rows, (3, 1)), 800.0, seed=1)))
        feeder.start()
        time.sleep(0.02)
        rep = fleet.swap_fleet("m", p2)
        feeder.join()
    out["swap_requests"] = len(handles)
    out["swap_dropped"] = int(sum(1 for h in handles if not h.done))
    out["swap_prepare_ms"] = round(rep.prepare_s * 1e3, 1)
    out["swap_commit_window_ms"] = round(rep.commit_window_s * 1e3, 3)
    out["swap_blackout_max_us"] = round(rep.max_blackout_s * 1e6, 1)
    out["swap_new_version_served"] = int(
        sum(1 for h in handles if h.version_tag == rep.new_tag))

    # crash drill: host death with requests in flight — re-dispatch
    # must leave nothing dropped or hung.  The engines get a per-flush
    # sleep floor so the backlog cannot fully drain between the last
    # submit and the kill (unpaced interpret-mode engines race the
    # ~µs submit loop and the drill's "in flight" premise evaporates —
    # the retried>0 contract in tests/test_bench_schema.py needs the
    # victim to actually hold work when it dies)
    with LutFleet(3, microbatch, deadline_s=0.05) as fleet:
        fleet.distribute_artifact(p1, "m")
        for r in fleet.replicas:
            b = r.registry.get("m").batcher

            def paced(x, _inner=b.serve_fn):
                time.sleep(0.01)
                return _inner(x)

            b.serve_fn = paced
        handles = [fleet.submit("m", r) for r in rows]
        victim = max(fleet.stats().items(),
                     key=lambda kv: kv[1]["outstanding"])[0]
        fleet.kill_replica(victim)
        done = 0
        for h in handles:
            try:
                h.result(timeout=60.0)
                done += 1
            except RuntimeError:
                pass
    shutil.rmtree(tmp, ignore_errors=True)
    out["crash_requests"] = len(handles)
    out["crash_dropped"] = int(len(handles) - done)
    out["crash_retried"] = int(sum(h.retries for h in handles))
    return out


def _bench_rpc_fleet(fast: bool):
    """Cross-process RPC fleet ledger (schema v9): what the socket
    transport costs over the in-process thread fleet.  Three series:

    * wire overhead — closed-loop serial submits (microbatch 1, so
      every request is its own flush) through a 1-replica THREAD fleet
      and a 1-worker PROCESS fleet over the length-prefixed socket RPC;
      ``wire_overhead_p50/p99_ms`` is the per-request latency delta
      (serialize + frame + TCP loopback + worker-side dispatch, both
      directions).  On a shared CPU the delta is noisy, so the pinned
      contracts are percentile ordering and zero drops, not the
      delta's sign.
    * slab-transfer throughput — one streamed FETCH_BEGIN/CHUNK/END
      artifact push into the worker's store, SHA-256 re-hashed by the
      worker on receipt (the admission gate), timed end-to-end.
    * death-detection latency — SIGKILL the worker process directly
      (no cooperative close), then measure how long the fleet takes to
      mark the replica down and bump the membership epoch.  The kernel
      closes the dead process's sockets, so on one box conn-loss
      usually fires before a heartbeat miss; the heartbeat prober is
      the backstop for true silence (a partition leaves the socket
      open), and ``heartbeat_interval_ms`` bounds that worst case.

    Hardware-independent contracts (pinned by
    tests/test_bench_schema.py): ``rpc_dropped == 0``, p50 <= p99 in
    both latency series, the slab transfer moved real bytes, and the
    silent death WAS detected."""
    from repro.artifact import save_artifact
    from repro.artifact.store import MANIFEST, SLAB_FILE
    from repro.launch.fleet import LutFleet
    from repro.launch.serve import build_lut_model

    microbatch = 1             # every submit is its own flush: the
    deadline_s = 2e-3          # closed loop times REQUESTS, not waits
    requests = 96 if fast else 256
    train_steps = 40 if fast else 150
    heartbeat_s = 0.05

    spec, tables, _ = build_lut_model(train_steps, seed=0)
    tmp = tempfile.mkdtemp(prefix="lut-bench-rpc-")
    p1 = save_artifact(tmp, tables, name="rpc-v1", spec=spec)
    rows = np.asarray(jax.random.randint(
        jax.random.key(11), (requests, spec.in_features), 0, 4), np.int32)

    def closed_loop(fleet):
        lat, dropped = [], 0
        for r in rows[:8]:     # warm: JIT + first-flush costs off-path
            fleet.submit("m", r).result(timeout=60.0)
        for r in rows:
            t0 = time.monotonic()
            h = fleet.submit("m", r)
            try:
                h.result(timeout=60.0)
                lat.append((time.monotonic() - t0) * 1e3)
            except RuntimeError:
                dropped += 1
        return lat, dropped

    with LutFleet(1, microbatch, deadline_s) as fleet:
        fleet.distribute_artifact(p1, "m")
        inproc_lat, inproc_drop = closed_loop(fleet)

    out = {
        "workers": 1,
        "microbatch": microbatch,
        "requests": requests,
        "inproc_p50_ms": round(float(np.percentile(inproc_lat, 50)), 3),
        "inproc_p99_ms": round(float(np.percentile(inproc_lat, 99)), 3),
    }

    with LutFleet(1, microbatch, deadline_s, transport="process",
                  heartbeat_s=heartbeat_s,
                  heartbeat_miss_limit=2) as fleet:
        fleet.distribute_artifact(p1, "m")
        rpc_lat, rpc_drop = closed_loop(fleet)

        # slab-transfer throughput: stream the artifact again, timed in
        # isolation (the worker pre-clears the destination, so a repeat
        # fetch is a pure transfer + re-hash, no register/warm cost)
        r = fleet._replica("r0")
        slab_bytes = sum(os.path.getsize(os.path.join(p1, f))
                         for f in (MANIFEST, SLAB_FILE))
        t0 = time.monotonic()
        r.registry.fetch(p1)
        xfer_s = time.monotonic() - t0

        # heartbeat detection: kill the worker process out from under
        # the fleet and wait for the prober to notice
        epoch0 = fleet.membership()["epoch"]
        r.proc.kill()
        t0 = time.monotonic()
        detect_s = None
        while time.monotonic() - t0 < 30.0:
            if "r0" not in fleet.healthy_replicas():
                detect_s = time.monotonic() - t0
                break
            time.sleep(0.005)
        detected = (detect_s is not None
                    and fleet.membership()["epoch"] > epoch0)
    shutil.rmtree(tmp, ignore_errors=True)

    out["rpc_p50_ms"] = round(float(np.percentile(rpc_lat, 50)), 3)
    out["rpc_p99_ms"] = round(float(np.percentile(rpc_lat, 99)), 3)
    out["wire_overhead_p50_ms"] = round(
        out["rpc_p50_ms"] - out["inproc_p50_ms"], 3)
    out["wire_overhead_p99_ms"] = round(
        out["rpc_p99_ms"] - out["inproc_p99_ms"], 3)
    out["rpc_dropped"] = int(inproc_drop + rpc_drop)
    out["slab_bytes"] = int(slab_bytes)
    out["slab_transfer_ms"] = round(xfer_s * 1e3, 2)
    out["slab_transfer_mb_s"] = round(slab_bytes / xfer_s / 2**20, 2)
    out["heartbeat_interval_ms"] = heartbeat_s * 1e3
    out["heartbeat_detect_ms"] = (
        round(detect_s * 1e3, 1) if detected else -1.0)
    return out


def _bench_scheduler(fast: bool):
    """SLO-tiered scoreboard scheduler ledger (schema v8): a mixed
    interactive/batch Poisson stream at 2x one replica's CALIBRATED
    steal-inclusive capacity, through tier-aware fleets of {1, 2, 4}
    replicas — per-tier p50/p99, interactive deadline attainment,
    typed-shed rate, and the work-steal counters (each replica also
    registers an idle sibling model, so a hot backlog exercises the
    StealGroup).  The intended shape of the series: r1 sheds (typed,
    never silent) while keeping admitted-attainment high, r2/r4 absorb
    the same stream without shedding.

    The replicas are threads on one CPU, so the replica series tracks
    tier-routing + admission overhead under overload, not parallel
    speedup.  The hardware-independent contracts pinned by
    tests/test_bench_schema.py: zero silent drops at every replica
    count (every non-served request is a typed ``DeadlineUnmeetable``)
    and attainment/shed-rate staying inside [0, 1]."""
    from repro.artifact import save_artifact
    from repro.launch.fleet import LutFleet
    from repro.launch.scheduler import (BATCH, interactive_tier,
                                        replay_tiered_open_loop,
                                        tier_report)
    from repro.launch.serve import build_lut_model

    # microbatch 4 x 4 ms floor puts the single-engine sustainable rate
    # (~1k req/s) far below what the open-loop submitter can offer on
    # this box (~4k req/s submit-bound through the fleet), so the
    # overload the section is ABOUT is genuinely reachable
    microbatch = 4
    deadline_s = 2e-3
    engine_floor_s = 4e-3
    requests = 512 if fast else 2048
    train_steps = 40 if fast else 150

    spec, tables_hot, _ = build_lut_model(train_steps, seed=0)
    _, tables_idle, _ = build_lut_model(train_steps, seed=1)
    tmp = tempfile.mkdtemp(prefix="lut-bench-sched-")
    p_hot = save_artifact(tmp, tables_hot, name="sched-hot", spec=spec)
    p_idle = save_artifact(tmp, tables_idle, name="sched-idle", spec=spec)
    rows = np.asarray(jax.random.randint(
        jax.random.key(9), (requests, spec.in_features), 0, 4), np.int32)
    warm_rows = rows[:2 * microbatch]

    def throttle(fleet):
        # pace every engine to a fixed per-flush floor.  Interpret-mode
        # kernels are GIL-bound Python: unpaced, the engines starve the
        # open-loop submitter thread and the calibrated "overload"
        # silently evaporates (zero sheds, nothing measured).  The
        # sleep floor releases the GIL, so the driver can actually
        # offer 1.5x sustainable and replicas genuinely serve flushes
        # (and stolen flushes) in parallel.
        for r in fleet.replicas:
            for mid in ("m", "m-idle"):
                b = r.registry.get(mid).batcher

                def paced(x, _inner=b.serve_fn):
                    t0 = time.monotonic()
                    out = _inner(x)
                    dt = engine_floor_s - (time.monotonic() - t0)
                    if dt > 0:
                        time.sleep(dt)
                    return out

                b.serve_fn = paced

    def build_fleet(n):
        fleet = LutFleet(n, microbatch, deadline_s,
                         slo_tiers=[interactive_tier(0.05), BATCH],
                         work_stealing=True)
        fleet.distribute_artifact(p_hot, "m")
        fleet.distribute_artifact(p_idle, "m-idle")  # the steal victim's
        # sibling: its batcher idles, so it can execute stolen flushes
        throttle(fleet)
        return fleet

    # calibrate the sustainable rate (microbatch / per-flush service)
    # on a 1-replica fleet, off the record
    with build_fleet(1) as fleet:
        for h in [fleet.submit("m", r, tier=BATCH) for r in warm_rows]:
            h.result(timeout=60.0)
        cap = fleet._replica("r0").registry.capacity("m")
    kernel_est_ms = cap["kernel_est_s"] * 1e3
    sustainable = cap["sustainable_req_s"]
    # overload is defined against the hot model's STEAL-INCLUSIVE
    # capacity on one replica (its own engine + the idle sibling it can
    # steal into = 2x the single-engine sustainable rate): at r1 even
    # stealing cannot absorb 2x, so admission must shed; added replicas
    # then absorb the same stream without sheds
    overload = 2.0
    rate = overload * 2 * sustainable
    it = interactive_tier(max(0.03, 8 * cap["kernel_est_s"]))
    pattern = [it, it, it, BATCH]        # 75% deadline-class

    out = {
        "microbatch": microbatch,
        "requests": requests,
        "replica_counts": [1, 2, 4],
        "kernel_est_ms": round(kernel_est_ms, 3),
        "sustainable_req_s": round(sustainable),
        "offered_req_s": round(rate),
        "overload_factor": overload,
        "interactive_frac": 0.75,
        "interactive_deadline_ms": round(it.deadline_s * 1e3, 3),
    }
    for n in (1, 2, 4):
        with build_fleet(n) as fleet:
            warm = [fleet.submit("m", r, tier=BATCH) for r in warm_rows]
            for h in warm:
                h.result(timeout=60.0)
            replay = replay_tiered_open_loop(
                fleet.client("m"), rows, rate=rate, tiers=pattern,
                seed=3, timeout_s=120.0)
            steals = sum(r.registry.steal_group.steals
                         for r in fleet.replicas)
            stolen = sum(r.registry.steal_group.stolen_requests
                         for r in fleet.replicas)
        rep = tier_report(replay)
        inter, batch = rep["interactive"], rep["batch"]
        served = sum(1 for h in replay.handles if h is not None)
        out[f"interactive_p50_ms_r{n}"] = round(inter["p50_ms"], 3)
        out[f"interactive_p99_ms_r{n}"] = round(inter["p99_ms"], 3)
        out[f"interactive_attainment_r{n}"] = round(
            inter["attainment"], 4)
        out[f"interactive_shed_rate_r{n}"] = round(
            inter["shed_rate"], 4)
        out[f"batch_p50_ms_r{n}"] = round(batch["p50_ms"], 3)
        out[f"batch_p99_ms_r{n}"] = round(batch["p99_ms"], 3)
        out[f"batch_throughput_req_s_r{n}"] = round(
            batch["throughput_req_s"])
        out[f"sheds_typed_r{n}"] = int(replay.sheds)
        out[f"silent_drops_r{n}"] = int(
            len(rows) - served - replay.sheds)
        out[f"hung_handles_r{n}"] = int(sum(
            1 for h in replay.handles if h is not None and not h.done))
        out[f"steals_r{n}"] = int(steals)
        out[f"stolen_requests_r{n}"] = int(stolen)
    shutil.rmtree(tmp, ignore_errors=True)
    return out


def _bench_connectivity(fast: bool) -> dict:
    """Connectivity-search ledger (schema v7): Alg.-2 population search
    wall-clock at {1, 2, 4} virtual devices (the seed axis shards over
    ``serving_mesh`` — virtual host devices share the same cores, so
    the series tracks SHARDING overhead on this box, not parallel
    speedup), the sharded-run bit-identity contract, and the
    headline searched-vs-random retrain accuracy delta per config."""
    from repro.configs import paper_models as PM
    from repro.data.loader import batch_iterator, train_test_split
    from repro.data.synthetic import make_dataset

    n_steps = 60 if fast else 150
    n_seeds = 4
    retrain_steps = 60 if fast else 150
    retrain_seeds = (10, 11) if fast else (10, 11, 12)
    data = train_test_split(make_dataset("jsc", n_samples=3000, seed=0))
    specs = [("tiny-jsc-f2", PM.tiny("jsc", degree=1, fan_in=2))]
    if not fast:
        specs.append(("jsc-m-lite-f4", PM.jsc_m_lite(degree=1)))

    def retrain(spec, conn, seed):
        init_state, step = LD.make_train_step(spec, lr=5e-3)
        state = init_state(jax.random.key(seed))
        if conn is not None:
            state["model"]["conn"] = conn
        jstep = jax.jit(step)
        it = batch_iterator(data["train"], 256, seed=seed)
        for _ in range(retrain_steps):
            state, _ = jstep(state, next(it))
        ev = jax.jit(LD.make_eval_step(spec))
        acc, _ = ev(state["model"], data["test"])
        return float(acc)

    devices_series = [1, 2, 4]
    out = {"n_steps": n_steps, "n_seeds": n_seeds,
           "retrain_steps": retrain_steps,
           "retrain_seeds": len(retrain_seeds),
           "devices_series": devices_series, "configs": []}
    for name, spec in specs:
        entry = {"name": name, "fan_in": int(spec.fan_in)}
        by_dev = {}
        for nd in devices_series:
            mesh = serving_mesh(nd) if nd > 1 else None
            it = batch_iterator(data["train"], 256, seed=3)
            t0 = time.perf_counter()
            masks, scores, _, _ = LD.search_connectivity_population(
                jax.random.key(3), spec, it, n_steps=n_steps,
                n_seeds=n_seeds, mesh=mesh, phase_frac=0.6, eps2=2e-3)
            jax.block_until_ready(scores)
            entry[f"search_wall_s_{nd}d"] = round(
                time.perf_counter() - t0, 3)
            by_dev[nd] = (masks, scores)
        for nd in (2, 4):
            entry[f"speedup_{nd}d_vs_1d"] = round(
                entry["search_wall_s_1d"] / entry[f"search_wall_s_{nd}d"],
                3)
        m1, s1 = by_dev[1]
        entry["bit_identical_sharded"] = all(
            all(bool(jnp.array_equal(a, b))
                for a, b in zip(m1, by_dev[nd][0]))
            and bool(jnp.array_equal(s1, by_dev[nd][1]))
            for nd in (2, 4))
        best_masks, best = LD.select_best_masks(m1, s1)
        entry["selected_seed"] = best
        conn = LD.masks_to_conn(best_masks, spec)
        rand = [retrain(spec, None, s) for s in retrain_seeds]
        opt = [retrain(spec, conn, s) for s in retrain_seeds]
        entry["acc_random_mean"] = round(float(np.mean(rand)), 4)
        entry["acc_searched_mean"] = round(float(np.mean(opt)), 4)
        entry["acc_delta_searched_vs_random"] = round(
            float(np.mean(opt) - np.mean(rand)), 4)
        out["configs"].append(entry)
    return out


def run(fast: bool = False, write_json: bool = False):
    batch = 1024 if fast else 4096
    iters = 3 if fast else 7
    results = [_bench_config(n, kw, batch, iters) for n, kw in CONFIGS]
    segmented = _bench_segmented(fast)
    serving = _bench_serving(fast)
    artifact = _bench_artifact(fast)
    fleet = _bench_fleet(fast)
    rpc_fleet = _bench_rpc_fleet(fast)
    scheduler = _bench_scheduler(fast)
    connectivity = _bench_connectivity(fast)

    cols = ["config", "B", "seed(i32)ms", "per-layer(u8)ms",
            "fused(u8)ms", "fused(i4)ms", "pipelined-ms",
            f"sharded-{results[0]['sharded_devices']}d-ms",
            "fused-vs-seed", "pipe-vs-serial"]
    rows = [[r["name"], r["batch"], r["seed_per_layer_int32_ms"],
             r["per_layer_packed_ms"], r["fused_packed_ms"],
             r["fused_int4_ms"], r["fused_pipelined_ms"],
             r["sharded_fused_ms"],
             f'{r["speedup_fused_vs_seed"]}x',
             f'{r["speedup_pipelined_vs_serial"]}x'] for r in results]
    print_table("LUT inference engine (CPU interpret proxy)", cols, rows)
    print_table(
        "VMEM ledger: int4 in-kernel unpack + tile pipeline",
        ["config", "tables(u8)B", "tables(i4)B", "residency-ratio",
         "vmem-fused(i4)B", "tile(grid)B", "tile(pipe)B",
         "block_b", "block_b(pipe)"],
        [[r["name"], r["table_bytes_packed"],
          r["table_bytes_int4"], r["table_residency_ratio_int4"],
          r["vmem_bytes_fused_int4"], r["vmem_tile_bytes_grid"],
          r["vmem_tile_bytes_pipelined"], r["block_b_tuned"],
          r["block_b_tuned_pipelined"]] for r in results])
    print_table(
        "segmented execution: over-budget net, fused segments vs per-layer",
        ["config", "B", "vmem/budget", "segs", "int4", "cut-w",
         "seg-ms", "per-layer-ms", "speedup", "HBM/cut-B"],
        [[segmented["name"], segmented["batch"],
          f'{segmented["over_budget_ratio"]}x', segmented["segments"],
          segmented["pack_int4"], segmented["cut_widths"][0],
          segmented["segmented_ms"], segmented["per_layer_ms"],
          f'{segmented["speedup_segmented_vs_per_layer"]}x',
          segmented["hbm_bytes_per_cut"][0]]])
    print_table(
        "deadline-flush serving (real threads, Poisson arrivals)",
        ["microbatch", "deadline_ms", "rate", "p50_ms", "p99_ms",
         "straggler_p99_ms", "p99_under_deadline"],
        [[serving["microbatch"], serving["deadline_ms"], serving["rate"],
          serving["p50_ms"], serving["p99_ms"],
          serving["straggler_p99_ms"], serving["p99_under_deadline"]]])
    print_table(
        "artifact store: compile-once cold load + hot-swap blackout",
        ["build_ms", "cold_load_ms", "cold_load_packed_ms", "speedup",
         "slab_bytes", "packed_table_bytes", "swap_dropped",
         "blackout_ms", "warm_ms"],
        [[artifact["build_from_scratch_ms"], artifact["cold_load_ms"],
          artifact["cold_load_packed_ms"],
          f'{artifact["speedup_cold_load_vs_build"]}x',
          artifact["artifact_slab_bytes"],
          artifact["table_bytes_loaded_packed"],
          artifact["swap_dropped"],
          artifact["swap_blackout_ms"], artifact["swap_warm_ms"]]])
    print_table(
        "serving fleet: replica routing + coordinated swap + crash drill",
        ["r1 req/s", "r2 req/s", "r4 req/s", "route-p99-us",
         "swap-commit-ms", "swap-blackout-us", "swap-dropped",
         "crash-dropped", "crash-retried"],
        [[fleet["throughput_req_s_r1"], fleet["throughput_req_s_r2"],
          fleet["throughput_req_s_r4"], fleet["route_overhead_p99_us"],
          fleet["swap_commit_window_ms"], fleet["swap_blackout_max_us"],
          fleet["swap_dropped"], fleet["crash_dropped"],
          fleet["crash_retried"]]])
    print_table(
        "RPC fleet: socket transport vs in-process (1 worker)",
        ["inproc-p50-ms", "rpc-p50-ms", "wire-p50-ms", "wire-p99-ms",
         "slab-MB/s", "hb-detect-ms", "dropped"],
        [[rpc_fleet["inproc_p50_ms"], rpc_fleet["rpc_p50_ms"],
          rpc_fleet["wire_overhead_p50_ms"],
          rpc_fleet["wire_overhead_p99_ms"],
          rpc_fleet["slab_transfer_mb_s"],
          rpc_fleet["heartbeat_detect_ms"], rpc_fleet["rpc_dropped"]]])
    print_table(
        "SLO scheduler: 2-tier Poisson @ 2x r1 capacity, {1,2,4} replicas",
        ["replicas", "int-p50-ms", "int-p99-ms", "attainment",
         "shed-rate", "batch-req/s", "steals", "silent-drops"],
        [[n, scheduler[f"interactive_p50_ms_r{n}"],
          scheduler[f"interactive_p99_ms_r{n}"],
          scheduler[f"interactive_attainment_r{n}"],
          scheduler[f"interactive_shed_rate_r{n}"],
          scheduler[f"batch_throughput_req_s_r{n}"],
          scheduler[f"steals_r{n}"], scheduler[f"silent_drops_r{n}"]]
         for n in (1, 2, 4)])
    print_table(
        "connectivity search: population sharding + searched-vs-random",
        ["config", "fan_in", "1d-s", "2d-s", "4d-s", "bit-ident",
         "acc-rand", "acc-searched", "delta"],
        [[c["name"], c["fan_in"], c["search_wall_s_1d"],
          c["search_wall_s_2d"], c["search_wall_s_4d"],
          c["bit_identical_sharded"], c["acc_random_mean"],
          c["acc_searched_mean"], c["acc_delta_searched_vs_random"]]
         for c in connectivity["configs"]])

    payload = {
        "bench": "lut_infer",
        "schema_version": 9,
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "fast": fast,
        "configs": results,
        "segmented": segmented,
        "serving": serving,
        "artifact": artifact,
        "fleet": fleet,
        "rpc_fleet": rpc_fleet,
        "scheduler": scheduler,
        "connectivity": connectivity,
    }
    if write_json:
        JSON_PATH.write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {JSON_PATH}")
    return {"rows": rows, "json": payload}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_lut_infer.json at the repo root")
    a = ap.parse_args()
    from repro.xla_env import use_compile_cache
    use_compile_cache()
    run(fast=a.fast, write_json=a.json)
