"""Multi-model LUT serving: compile-once artifacts + hot-swap registry.

The deployment story in three stages, mirroring the paper's synthesis
-> bitstream -> serve split:

  1. **compile once** — each model variant is trained + synthesised to
     truth tables ONCE and persisted as a content-addressed artifact
     (repro/artifact: packed table slabs, cached routing matrices,
     quant/spec metadata, per-slab SHA-256);
  2. **serve many** — every later process start COLD-LOADS the
     artifacts (memmap -> jnp, milliseconds, no trainer import) and
     registers them in a launch/registry.ModelRegistry: one fused
     lut_gather engine + one deadline-flush MicroBatcher per model id,
     all serving concurrently from one process;
  3. **swap live** — a new artifact version warms off-path and replaces
     its model id atomically: in-flight requests drain on the old
     tables, racers re-route, ZERO requests drop, and the measured
     blackout is the microseconds the routing dict swap holds a lock.

Usage — compile-once -> serve-many
----------------------------------
First run trains both variants and writes artifacts; every later run
with the same ``--artifact-dir`` skips training entirely:

    PYTHONPATH=src python examples/lut_serve.py \
        --artifact-dir /tmp/lut-artifacts --train-steps 150

    # later (cold start, no retraining — loads in milliseconds):
    PYTHONPATH=src python examples/lut_serve.py \
        --artifact-dir /tmp/lut-artifacts

Sharded serving: ``--shards N`` runs every engine under shard_map on a
1-D data mesh (batch sharded, tables replicated — bit-exact vs the
single-device oracle, tests/test_lut_sharded.py).  On CPU expose
virtual devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python examples/lut_serve.py --shards 4

Fleet serving (launch/fleet.py) scales the SAME artifacts across
replica "hosts": a ``LutFleet`` stands N registry replicas behind a
least-outstanding router, ships each artifact to every replica's local
store, and admits a copy only after re-verifying its manifest hashes
(``repro.artifact.verify_artifact`` — the content-addressed ids make
this free).  Version upgrades are TWO-PHASE: ``prepare_swap`` warms the
new engine off-path on every replica, ``commit_swap`` cuts them all
over in one tight loop, and every response echoes the artifact id that
served it (``FleetHandle.version_tag``).  A replica crash mid-request
re-dispatches transparently — zero requests dropped
(tests/test_fleet.py is the fault-injection harness).  Try it:

    PYTHONPATH=src python -m repro.launch.serve --lut --replicas 4
    PYTHONPATH=src python -m repro.launch.serve --lut --fleet-swap-demo \
        --replicas 2 --requests 2048 --rate 1000

Segmented execution: each engine's shape is chosen by a cost model,
not a binary fits/doesn't-fit gate.  ``ops.plan_segments`` partitions
the layer list into the fewest segments whose table slabs fit the
fused VMEM budget — models this size plan to ONE segment (the classic
fully fused kernel); a deeper/wider net plans to N fused segments
chained through HBM, each double-buffering its tile DMAs, paying only
``2 * batch * cut_width * 4`` HBM bytes per cut instead of the ~5x
per-layer cliff.  The chosen plan ships INSIDE the artifact manifest
(with the per-segment tuned ``block_b``), so stage 2's cold loads
adopt it without re-planning or re-tuning, and the registry reports it
per model (``stats()["<id>"]["exec_mode"]``) — the fusion decision is
observable, never silent.

Two-tier SLO serving (launch/scheduler.py): under overload the plain
stack degrades everyone uniformly — the scoreboard scheduler instead
splits traffic into an **interactive** tier (hard per-request deadline)
and a **batch** tier (best-effort).  Each model's batcher fills from a
scoreboard (a pending-matrix slot array, not a FIFO): deadline-class
requests issue earliest-deadline-first, batch requests backfill the
remaining slots.  A deadline-class request whose queue-depth x
kernel-time estimate provably misses its deadline is SHED at submit
with the typed ``DeadlineUnmeetable`` (never a silent drop), and an
idle model's batcher steals flushes from a backlogged sibling in the
same registry.  Drive the mixed Poisson stream at 1.5x the sustainable
rate and the interactive tier keeps >= 95% deadline attainment while
batch traffic absorbs the overload:

    PYTHONPATH=src python -m repro.launch.serve --lut --slo-tiers \
        --interactive-deadline-ms 25 --interactive-frac 0.5 \
        --requests 4096 --rate 30000
    # same stream, tier-aware fleet routing across 4 replicas:
    PYTHONPATH=src python -m repro.launch.serve --lut --slo-tiers \
        --replicas 4 --requests 4096 --rate 30000

Knobs: --microbatch (flush size = engine batch), --deadline-ms (max
straggler queueing delay), --rate (offered Poisson load per model),
--requests (stream length per model).  Reports per-model p50/p95/p99
latency, throughput, accuracy, and the hot-swap blackout/drop count.
"""
import argparse
import os
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.artifact import find_artifacts, load_artifact, save_artifact
from repro.core.cost_model import model_cost
from repro.kernels.lut_gather import ops as lg_ops
from repro.launch.batching import latency_percentiles_ms, replay_open_loop
from repro.launch.registry import ModelRegistry
from repro.launch.serve import build_lut_model, lut_accuracy, lut_dataset
from repro.parallel.sharding import serving_mesh

# the fleet: one registry, several architectures + a v2 of the first
# (the hot-swap payload).  kw feeds launch/serve.build_lut_model.
MODEL_DEFS = {
    "jsc-base":    dict(fan_in=3, adder_width=2, seed=0),
    "jsc-lite":    dict(fan_in=2, adder_width=1, seed=0),
    "jsc-base-v2": dict(fan_in=3, adder_width=2, seed=99),
}


def compile_or_load(art_dir: str, train_steps: int):
    """Stage 1+2: per model id, cold-load its artifact when present,
    otherwise train-synthesise-save then load THROUGH the artifact (so
    every serving path below runs off the deployable format)."""
    arts = {}
    for mid, kw in MODEL_DEFS.items():
        subdir = os.path.join(art_dir, mid)
        t0 = time.monotonic()
        if find_artifacts(subdir):
            # unpack_int4=False: int4 slabs stay two-codes-per-byte
            # from disk into the fused kernel (in-kernel nibble unpack)
            art = load_artifact(subdir, unpack_int4=False)
            print(f"  {mid}: cold-loaded {art.artifact_id[:12]} in "
                  f"{(time.monotonic() - t0) * 1e3:.1f} ms (no training)")
        else:
            spec, tables, _ = build_lut_model(train_steps, **kw)
            # persist the execution plan with the tables: later cold
            # loads skip re-planning and the block_b sweep entirely
            plan = lg_ops.plan_segments(tables, n_in0=spec.in_features)
            path = save_artifact(subdir, tables, name=mid, spec=spec,
                                 plan=plan,
                                 provenance=dict(kw,
                                                 train_steps=train_steps))
            art = load_artifact(path, unpack_int4=False)
            print(f"  {mid}: trained+compiled in "
                  f"{time.monotonic() - t0:.1f} s -> "
                  f"{art.artifact_id[:12]} "
                  f"(modeled FPGA: {model_cost(spec)})")
        arts[mid] = art
    return arts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifact-dir", default=None,
                    help="artifact store (default: fresh tempdir, i.e. "
                         "compile on every run)")
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--microbatch", type=int, default=256)
    ap.add_argument("--requests", type=int, default=2048,
                    help="stream length per served model")
    ap.add_argument("--rate", type=float, default=20_000.0,
                    help="offered Poisson load per model (req/s)")
    ap.add_argument("--deadline-ms", type=float, default=2.0)
    ap.add_argument("--shards", type=int, default=0,
                    help="shard_map every engine over N devices")
    args = ap.parse_args()

    art_dir = args.artifact_dir or tempfile.mkdtemp(prefix="lut-artifacts-")
    print(f"artifact store: {art_dir}")
    arts = compile_or_load(art_dir, args.train_steps)

    data = lut_dataset(seed=0)
    served_ids = ["jsc-base", "jsc-lite"]
    rng = np.random.default_rng(0)
    streams = {}
    for mid in served_ids:
        fq = arts[mid].spec.layer_specs()[0].in_quant
        idx = rng.integers(0, data["test"]["x"].shape[0], args.requests)
        streams[mid] = (idx, np.asarray(fq.to_code(fq.clip(
            jnp.asarray(np.asarray(data["test"]["x"])[idx])))))

    mesh = serving_mesh(args.shards) if args.shards else None
    with ModelRegistry(args.microbatch, args.deadline_ms / 1e3,
                       mesh=mesh) as reg:
        for mid in served_ids:
            reg.register(mid, arts[mid])
        print(f"registry serving {reg.model_ids()} "
              f"(shards={args.shards or 1})")
        for mid in served_ids:
            print(f"  {mid}: {reg.get(mid).plan.describe()}")

        handles = {mid: [] for mid in served_ids}
        t0 = time.monotonic()
        feeders = [threading.Thread(
            target=lambda m=mid: handles[m].extend(replay_open_loop(
                reg.client(m), streams[m][1], args.rate)))
            for mid in served_ids]
        for f in feeders:
            f.start()
        # stage 3: hot-swap jsc-base to v2 mid-stream, under full load
        # on BOTH models
        time.sleep(0.4 * args.requests / args.rate)
        rep = reg.swap("jsc-base", arts["jsc-base-v2"])
        for f in feeders:
            f.join()
        span = time.monotonic() - t0

        print(f"hot-swap jsc-base v{rep.old_version}->v{rep.new_version}: "
              f"warm {rep.warm_s * 1e3:.1f} ms off-path, blackout "
              f"{rep.blackout_s * 1e6:.1f} us, "
              f"{rep.drained_requests} drained on old engine")
        for mid in served_ids:
            hs = handles[mid]
            failed = sum(1 for h in hs if h.failed)
            dropped = args.requests - len(hs)
            p50, p95, p99 = latency_percentiles_ms(hs)
            acc = lut_accuracy(hs, data, streams[mid][0])
            print(f"  {mid}: {len(hs)}/{args.requests} served, "
                  f"{failed} failed, {dropped} dropped | p50 {p50:.2f} / "
                  f"p95 {p95:.2f} / p99 {p99:.2f} ms | acc {acc:.4f}")
        print(f"aggregate throughput "
              f"{sum(len(h) for h in handles.values()) / span:,.0f} req/s "
              f"across {len(served_ids)} concurrent models")
    dev = jax.devices()[0]
    mode = ("compiled Pallas kernels" if dev.platform == "tpu"
            else "Pallas interpreter")
    print(f"(measured on {dev.device_kind} ({dev.platform}) "
          f"x{jax.device_count()}, {mode})")


if __name__ == "__main__":
    main()
