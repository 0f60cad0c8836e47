"""Batched serving launcher: LM prefill + decode loop, or LUT-mode.

Serves any registered architecture (reduced configs on CPU) with a
continuous-batching-style loop: one prefill builds the KV cache /
recurrent state, then ``serve_step`` decodes token-by-token for the
whole batch.  The decode path is exactly what the ``decode_32k`` /
``long_500k`` dry-run cells lower onto the production mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
        --smoke --batch 4 --prompt-len 32 --gen 16

``--lut`` switches to the LUT-DNN serving stack instead: a tiny model
is trained + synthesised to truth tables, and requests flow through
the REAL async front-end (launch/batching.MicroBatcher — threaded
queue, deadline-based microbatch flush) into the fused lut_gather
engine, optionally shard_map'ed over ``--shards`` devices (batch
sharded, tables replicated).  ``build_lut_model`` / ``run_lut_load``
here are the canonical assembly, reused by examples/lut_serve.py and
benchmarks/lut_infer_bench.py.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --lut --shards 4 \
        --microbatch 256 --deadline-ms 2 --requests 2048 --rate 50000

Compile-once deployment (repro/artifact): ``--save-artifact`` writes
the synthesised network to ``--artifact-dir`` as a content-addressed
artifact after training; a later run with the same ``--artifact-dir``
COLD-LOADS it (no training, no synthesis — milliseconds) and serves
identically bit-for-bit.  ``--swap-demo`` exercises the multi-model
path end to end: two artifact versions are compiled, v1 serves a live
Poisson stream through launch/registry.ModelRegistry, and v2 is
hot-swapped in mid-stream — zero requests dropped, blackout reported.

    PYTHONPATH=src python -m repro.launch.serve --lut \
        --artifact-dir /tmp/lut-artifacts --save-artifact   # compile
    PYTHONPATH=src python -m repro.launch.serve --lut \
        --artifact-dir /tmp/lut-artifacts                   # cold-load
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm as LM
from repro.models import registry as R


# ---------------------------------------------------------------------------
# LUT-mode serving assembly (shared with examples/ and benchmarks/)
# ---------------------------------------------------------------------------

def lut_dataset(seed: int = 0):
    """The deterministic jsc dataset the LUT serving stack trains and
    evaluates on — separate from training so an artifact cold-load can
    still score accuracy without touching the trainer."""
    from repro.data.loader import train_test_split
    from repro.data.synthetic import make_dataset

    return train_test_split(make_dataset("jsc", n_samples=4000, seed=seed))


def build_lut_model(train_steps: int = 150, fan_in: int = 3,
                    adder_width: int = 2, seed: int = 0):
    """Train + synthesise a tiny LUT-DNN (a real deployment loads the
    tables from disk — see ``load_or_build_lut_model``).  Returns
    (spec, tables, data)."""
    from repro.configs import paper_models as PM
    from repro.core import lut_synth as LS
    from repro.core import lutdnn as LD
    from repro.data.loader import batch_iterator

    data = lut_dataset(seed)
    spec = PM.tiny("jsc", degree=1, fan_in=fan_in, adder_width=adder_width)
    init_state, step = LD.make_train_step(spec, lr=5e-3)
    state = init_state(jax.random.key(seed))
    jstep = jax.jit(step)
    it = batch_iterator(data["train"], 256, seed=seed)
    for _ in range(train_steps):
        state, _ = jstep(state, next(it))
    tables = LS.synthesise(state["model"], spec)
    return spec, tables, data


def load_or_build_lut_model(train_steps: int = 150,
                            artifact_dir: str = None,
                            save: bool = False, seed: int = 0):
    """The compile-once entry: cold-load the newest artifact under
    ``artifact_dir`` when one exists (NO training — the ≥10x cheaper
    path the benchmark tracks), otherwise train + synthesise and
    optionally persist the result.  Returns
    (spec, source, data, origin) where ``source`` feeds
    ``ops.make_network_fn`` directly (an Artifact or a table list) and
    ``origin`` is "artifact" | "trained" | "trained+saved"."""
    from repro.artifact import find_artifacts, load_artifact

    if artifact_dir and find_artifacts(artifact_dir):
        t0 = time.monotonic()
        # unpack_int4=False: int4 slabs stay two-codes-per-byte all the
        # way into the fused kernel (in-kernel nibble unpack), so the
        # serving process keeps the halved table residency
        art = load_artifact(artifact_dir, unpack_int4=False)
        dt = time.monotonic() - t0
        spec = art.spec
        if spec is None:
            raise SystemExit(
                f"artifact {art.artifact_id[:12]} carries no ModelSpec — "
                f"re-save it with spec= to serve through this launcher")
        print(f"cold-loaded artifact {art.artifact_id[:12]} "
              f"({art.path}) in {dt * 1e3:.1f} ms — no retraining")
        return spec, art, lut_dataset(seed), "artifact"

    spec, tables, data = build_lut_model(train_steps, seed=seed)
    if save and artifact_dir:
        from repro.artifact import save_artifact
        from repro.kernels.lut_gather import ops as lg_ops
        # ship the execution plan with the model: cold loads adopt it
        # and skip both re-planning and the tune_block_b sweep (the
        # plan lives outside the hashed content — same artifact id)
        plan = lg_ops.plan_segments(tables, n_in0=spec.in_features)
        path = save_artifact(
            artifact_dir, tables, name=spec.name.replace(" ", ""),
            spec=spec, plan=plan,
            provenance={"train_steps": train_steps,
                        "seed": seed, "dataset": "jsc"})
        print(f"saved artifact {path}")
        return spec, tables, data, "trained+saved"
    return spec, tables, data, "trained"


def run_lut_load(serve_fn, fq, data, n_requests: int, microbatch: int,
                 deadline_s: float, rate: float, seed: int = 0):
    """Drive a Poisson open-loop request stream through the deadline-
    flush batcher into ``serve_fn``.  Returns (handles, batcher, idx):
    handles carry real measured latencies, the batcher carries flush
    telemetry, and ``idx`` are the test-set rows served (needed to
    align labels in ``lut_accuracy``)."""
    from repro.launch.batching import MicroBatcher, replay_open_loop

    rng = np.random.default_rng(seed)
    n_test = data["test"]["x"].shape[0]
    idx = rng.integers(0, n_test, n_requests)
    x_all = np.asarray(data["test"]["x"])[idx]
    codes_all = np.asarray(fq.to_code(fq.clip(jnp.asarray(x_all))))

    def engine(batch_np):
        out = serve_fn(jnp.asarray(batch_np))
        return np.asarray(jax.block_until_ready(out))

    with MicroBatcher(engine, microbatch, deadline_s,
                      n_features=codes_all.shape[1]) as mb:
        handles = replay_open_loop(mb, codes_all, rate, seed=seed)
    return handles, mb, idx


def lut_accuracy(handles, data, idx) -> float:
    """Classification accuracy of served results — ONE batched decode
    (stack every output row, dequantize, argmax), not one dispatch per
    request.  Handles whose batch failed in the engine are excluded
    (their result() re-raises); nan when nothing succeeded."""
    from repro.core import lut_synth as LS

    ok = [(h, i) for h, i in zip(handles, np.asarray(idx))
          if h.done and not h.failed]
    if not ok:
        return float("nan")
    out = jnp.asarray(np.stack([h.result() for h, _ in ok]))
    pred = np.asarray(jnp.argmax(LS.OUTPUT_QUANT.from_code(out), -1))
    y = np.asarray(data["test"]["y"])[[i for _, i in ok]]
    return float((pred == y).mean())


def report_lut_serving(header: str, handles, mb, acc: float,
                       span: float) -> None:
    """Shared latency/throughput/flush-telemetry report (used by this
    launcher and examples/lut_serve.py)."""
    from repro.launch.batching import latency_percentiles_ms

    p50, p95, p99 = latency_percentiles_ms(handles)
    fills = [f.fill for f in mb.flushes]
    print(header)
    print(f"  latency p50 {p50:.2f} ms / p95 {p95:.2f} ms / "
          f"p99 {p99:.2f} ms")
    print(f"  throughput {len(handles) / span:,.0f} req/s over "
          f"{len(mb.flushes)} flushes (mean fill {np.mean(fills):.1f}, "
          f"{sum(f.deadline_hit for f in mb.flushes)} "
          f"deadline-triggered), accuracy {acc:.4f}")


def drive_lut_serving(serve_fn, spec, data, *, requests: int,
                      microbatch: int, deadline_ms: float, rate: float,
                      header: str):
    """Warm the engine, run the open-loop load, print the shared
    report.  Returns (handles, batcher) for callers that inspect
    telemetry further."""
    # warm the compile cache outside the measured window
    jax.block_until_ready(serve_fn(
        jnp.zeros((microbatch, spec.in_features), jnp.int32)))
    fq = spec.layer_specs()[0].in_quant
    t0 = time.monotonic()
    handles, mb, idx = run_lut_load(
        serve_fn, fq, data, requests, microbatch, deadline_ms / 1e3, rate)
    span = time.monotonic() - t0
    report_lut_serving(header, handles, mb,
                       lut_accuracy(handles, data, idx), span)
    return handles, mb


def run_swap_demo(args, mesh) -> None:
    """Compile two artifact versions, serve v1 through the multi-model
    registry under live Poisson load, hot-swap to v2 mid-stream.
    Success criteria printed at the end: zero dropped requests, the
    swap blackout, and which engine served each phase."""
    import tempfile
    import threading

    from repro.artifact import save_artifact
    from repro.launch.batching import replay_open_loop
    from repro.launch.registry import ModelRegistry

    art_dir = args.artifact_dir or tempfile.mkdtemp(prefix="lut-artifacts-")
    spec, tables_v1, data = build_lut_model(args.lut_train_steps, seed=0)
    _, tables_v2, _ = build_lut_model(args.lut_train_steps, seed=1)
    paths = [save_artifact(art_dir, t, name=f"tiny-jsc-v{i + 1}",
                           spec=spec, provenance={"seed": i})
             for i, t in enumerate((tables_v1, tables_v2))]
    print(f"compiled artifacts:\n  v1 {paths[0]}\n  v2 {paths[1]}")

    fq = spec.layer_specs()[0].in_quant
    rng = np.random.default_rng(0)
    idx = rng.integers(0, data["test"]["x"].shape[0], args.requests)
    codes = np.asarray(fq.to_code(fq.clip(
        jnp.asarray(np.asarray(data["test"]["x"])[idx]))))

    with ModelRegistry(args.microbatch, args.deadline_ms / 1e3,
                       mesh=mesh) as reg:
        reg.register("tiny-jsc", paths[0])
        handles: list = []
        t0 = time.monotonic()
        feeder = threading.Thread(target=lambda: handles.extend(
            replay_open_loop(reg.client("tiny-jsc"), codes, args.rate)))
        feeder.start()
        # land the swap mid-stream: after ~40% of the offered window
        time.sleep(0.4 * args.requests / args.rate)
        rep = reg.swap("tiny-jsc", paths[1])
        feeder.join()
        span = time.monotonic() - t0

    failed = sum(1 for h in handles if h.failed)
    acc = lut_accuracy(handles, data, idx)
    print(f"hot-swap demo: {len(handles)}/{args.requests} served, "
          f"{failed} failed, {args.requests - len(handles)} dropped")
    print(f"  swap v{rep.old_version}->v{rep.new_version}: warm "
          f"{rep.warm_s * 1e3:.1f} ms off-path, blackout "
          f"{rep.blackout_s * 1e6:.1f} us, drained "
          f"{rep.drained_requests} in-flight on old engine")
    print(f"  throughput {len(handles) / span:,.0f} req/s, "
          f"post-swap accuracy (mixed stream) {acc:.4f}")


def _fleet_artifact_path(args, spec, source, origin) -> str:
    """Fleet distribution ships BYTES (copy + hash-verify at every
    replica), so the model must exist as an on-disk artifact; persist
    an in-memory synthesis result to a tempdir when needed."""
    import tempfile

    if hasattr(source, "path"):            # a loaded Artifact
        return source.path
    from repro.artifact import save_artifact
    out = args.artifact_dir or tempfile.mkdtemp(prefix="lut-fleet-src-")
    path = save_artifact(out, source, name=spec.name.replace(" ", ""),
                         spec=spec, provenance={"origin": origin})
    print(f"persisted artifact for fleet distribution: {path}")
    return path


def run_fleet_serving(args, mesh) -> None:
    """Serve the Poisson load through an N-replica fleet: artifact
    distributed + hash-verified on every replica, requests routed
    least-outstanding, every response tagged with the serving artifact
    id."""
    from repro.launch.batching import replay_open_loop
    from repro.launch.fleet import LutFleet

    spec, source, data, origin = load_or_build_lut_model(
        args.lut_train_steps, artifact_dir=args.artifact_dir,
        save=args.save_artifact)
    path = _fleet_artifact_path(args, spec, source, origin)

    fq = spec.layer_specs()[0].in_quant
    rng = np.random.default_rng(0)
    idx = rng.integers(0, data["test"]["x"].shape[0], args.requests)
    codes = np.asarray(fq.to_code(fq.clip(
        jnp.asarray(np.asarray(data["test"]["x"])[idx]))))

    with LutFleet(args.replicas, args.microbatch,
                  args.deadline_ms / 1e3, mesh=mesh) as fleet:
        dist = fleet.distribute_artifact(path, "m")
        print(f"distributed to {sorted(dist)}: "
              f"{ {k: d.admitted for k, d in dist.items()} }")
        t0 = time.monotonic()
        handles = replay_open_loop(fleet.client("m"), codes, args.rate)
        span = time.monotonic() - t0
        stats = fleet.stats()

    from repro.launch.batching import latency_percentiles_ms
    p50, p95, p99 = latency_percentiles_ms(handles)
    acc = lut_accuracy(handles, data, idx)
    print(f"lut-serve[fleet x{args.replicas}, {origin}] "
          f"microbatch={args.microbatch} deadline={args.deadline_ms}ms "
          f"rate={args.rate:,.0f}/s:")
    print(f"  latency p50 {p50:.2f} ms / p95 {p95:.2f} ms / "
          f"p99 {p99:.2f} ms")
    print(f"  throughput {len(handles) / span:,.0f} req/s, "
          f"accuracy {acc:.4f}, per-replica served "
          f"{ {k: v['served'] for k, v in stats.items()} }")


def run_fleet_swap_demo(args, mesh) -> None:
    """Two artifact versions, an N-replica fleet under live Poisson
    load, and a TWO-PHASE coordinated swap mid-stream: prepare warms
    the new engine off-path on every replica, commit cuts them all
    over — zero dropped requests, every response tagged old or new,
    post-commit every replica on the new id."""
    import tempfile
    import threading

    from repro.artifact import save_artifact
    from repro.launch.batching import replay_open_loop
    from repro.launch.fleet import LutFleet

    replicas = args.replicas or 2
    art_dir = args.artifact_dir or tempfile.mkdtemp(prefix="lut-artifacts-")
    spec, tables_v1, data = build_lut_model(args.lut_train_steps, seed=0)
    _, tables_v2, _ = build_lut_model(args.lut_train_steps, seed=1)
    paths = [save_artifact(art_dir, t, name=f"tiny-jsc-v{i + 1}",
                           spec=spec, provenance={"seed": i})
             for i, t in enumerate((tables_v1, tables_v2))]
    print(f"compiled artifacts:\n  v1 {paths[0]}\n  v2 {paths[1]}")

    fq = spec.layer_specs()[0].in_quant
    rng = np.random.default_rng(0)
    idx = rng.integers(0, data["test"]["x"].shape[0], args.requests)
    codes = np.asarray(fq.to_code(fq.clip(
        jnp.asarray(np.asarray(data["test"]["x"])[idx]))))

    with LutFleet(replicas, args.microbatch,
                  args.deadline_ms / 1e3, mesh=mesh) as fleet:
        fleet.distribute_artifact(paths[0], "m")
        handles: list = []
        t0 = time.monotonic()
        feeder = threading.Thread(target=lambda: handles.extend(
            replay_open_loop(fleet.client("m"), codes, args.rate)))
        feeder.start()
        time.sleep(0.1 * args.requests / args.rate)
        prepared = fleet.prepare_swap("m", paths[1])
        rep = fleet.commit_swap(prepared)
        feeder.join()
        span = time.monotonic() - t0
        tags = fleet.admitted_tags("m")

    dropped = args.requests - sum(1 for h in handles if h.done)
    by_tag: dict = {}
    for h in handles:
        by_tag[h.version_tag[:12]] = by_tag.get(h.version_tag[:12], 0) + 1
    acc = lut_accuracy(handles, data, idx)
    print(f"fleet swap demo (x{replicas} replicas): "
          f"{len(handles)}/{args.requests} served, {dropped} dropped")
    print(f"  prepare {rep.prepare_s * 1e3:.1f} ms off-path (all "
          f"replicas), commit window {rep.commit_window_s * 1e3:.2f} ms, "
          f"max blackout {rep.max_blackout_s * 1e6:.1f} us, "
          f"{rep.total_drained} drained on old engines")
    print(f"  responses by version tag: {by_tag}")
    print(f"  post-commit fleet consistent: "
          f"{len(set(tags.values())) == 1} "
          f"(all on {rep.new_tag[:12]})")
    print(f"  throughput {len(handles) / span:,.0f} req/s, "
          f"accuracy (mixed stream) {acc:.4f}")


def run_slo_serving(args, mesh) -> None:
    """Two-tier SLO serving: a mixed interactive/batch Poisson stream
    through the scoreboard scheduler (launch/scheduler.py) — EDF issue
    order with batch backfill, admission control shedding provably-late
    interactive requests with the typed DeadlineUnmeetable, and
    work-stealing across sibling batchers.  One host by default;
    ``--replicas N`` runs the same stream through a tiered fleet."""
    from repro.launch.fleet import LutFleet
    from repro.launch.registry import ModelRegistry
    from repro.launch.scheduler import (BATCH, interactive_tier,
                                        replay_tiered_open_loop,
                                        tier_report)

    spec, source, data, origin = load_or_build_lut_model(
        args.lut_train_steps, artifact_dir=args.artifact_dir,
        save=args.save_artifact)
    fq = spec.layer_specs()[0].in_quant
    rng = np.random.default_rng(0)
    idx = rng.integers(0, data["test"]["x"].shape[0], args.requests)
    codes = np.asarray(fq.to_code(fq.clip(
        jnp.asarray(np.asarray(data["test"]["x"])[idx]))))

    it = interactive_tier(args.interactive_deadline_ms / 1e3)
    tiers = [it, BATCH]
    # Bresenham interleave: ~interactive_frac of the stream is
    # deadline-class, evenly mixed with best-effort traffic
    k = max(0, min(10, round(args.interactive_frac * 10)))
    pattern = [it if (i * k) // 10 != ((i + 1) * k) // 10 else BATCH
               for i in range(10)]
    if not any(t is it for t in pattern):
        pattern = [BATCH]

    if args.replicas:
        path = _fleet_artifact_path(args, spec, source, origin)
        with LutFleet(args.replicas, args.microbatch,
                      args.deadline_ms / 1e3, mesh=mesh,
                      slo_tiers=tiers, work_stealing=True) as fleet:
            fleet.distribute_artifact(path, "m")
            replay = replay_tiered_open_loop(
                fleet.client("m"), codes, args.rate, pattern)
        where = f"fleet x{args.replicas}"
    else:
        with ModelRegistry(args.microbatch, args.deadline_ms / 1e3,
                           mesh=mesh, slo_tiers=tiers,
                           work_stealing=True) as reg:
            reg.register("m", source)
            replay = replay_tiered_open_loop(
                reg.client("m"), codes, args.rate, pattern)
        where = "1 host"

    report = tier_report(replay)
    print(f"lut-serve[slo-tiers, {where}, {origin}] "
          f"microbatch={args.microbatch} flush-deadline="
          f"{args.deadline_ms}ms rate={args.rate:,.0f}/s "
          f"interactive-slo={args.interactive_deadline_ms}ms:")
    for name, ent in sorted(report.items()):
        line = (f"  {name:<12} offered {ent['offered']:>6} shed "
                f"{ent['shed']:>5} ({ent['shed_rate'] * 100:.1f}%) "
                f"p50 {ent['p50_ms']:.2f} ms p99 {ent['p99_ms']:.2f} ms "
                f"{ent['throughput_req_s']:,.0f} req/s")
        if "attainment" in ent:
            line += f" attainment {ent['attainment'] * 100:.1f}%"
        print(line)
    hung = sum(1 for h in replay.handles if h is not None and not h.done)
    print(f"  sheds all typed, silent drops 0, hung handles {hung}")


def serve_lut(args) -> None:
    from repro.kernels.lut_gather import ops as lg_ops
    from repro.parallel.sharding import serving_mesh

    mesh = serving_mesh(args.shards) if args.shards else None
    if args.slo_tiers:
        run_slo_serving(args, mesh)
        return
    if args.fleet_swap_demo:
        run_fleet_swap_demo(args, mesh)
        return
    if args.swap_demo:
        run_swap_demo(args, mesh)
        return
    if args.replicas:
        run_fleet_serving(args, mesh)
        return

    spec, source, data, origin = load_or_build_lut_model(
        args.lut_train_steps, artifact_dir=args.artifact_dir,
        save=args.save_artifact)
    # plan-driven engine choice: fused when the slabs fit VMEM, a chain
    # of fused segments when they do not (a persisted plan in an
    # artifact manifest is adopted as-is, skipping re-plan + tune)
    serve_fn = lg_ops.make_network_fn(source, block_b=args.microbatch,
                                      mesh=mesh)
    print(f"  {serve_fn.execution_plan.describe()} "
          f"lookup_row_chunks={serve_fn.lookup_row_chunks} "
          f"routing_macs_per_row={serve_fn.routing_macs_per_row}")
    drive_lut_serving(
        serve_fn, spec, data, requests=args.requests,
        microbatch=args.microbatch, deadline_ms=args.deadline_ms,
        rate=args.rate,
        header=f"lut-serve[{origin}] shards={args.shards or 1} "
               f"microbatch={args.microbatch} deadline={args.deadline_ms}ms "
               f"rate={args.rate:,.0f}/s:")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--lut", action="store_true",
                    help="serve a synthesised LUT-DNN through the async "
                         "deadline-flush batcher (optionally sharded)")
    ap.add_argument("--lut-train-steps", type=int, default=150)
    ap.add_argument("--artifact-dir", default=None,
                    help="compile-once artifact store: cold-load the "
                         "newest artifact here instead of retraining")
    ap.add_argument("--save-artifact", action="store_true",
                    help="persist the synthesised network to "
                         "--artifact-dir after training")
    ap.add_argument("--swap-demo", action="store_true",
                    help="multi-model registry demo: hot-swap a second "
                         "artifact version under live load")
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve through an N-replica fleet (router + "
                         "verified artifact distribution; 0 = one host)")
    ap.add_argument("--fleet-swap-demo", action="store_true",
                    help="fleet demo: two-phase coordinated hot-swap "
                         "across all replicas under live load")
    ap.add_argument("--slo-tiers", action="store_true",
                    help="two-tier SLO serving through the scoreboard "
                         "scheduler: interactive (hard deadline, EDF, "
                         "admission-controlled) + batch (best-effort "
                         "backfill), with work-stealing")
    ap.add_argument("--interactive-deadline-ms", type=float, default=25.0,
                    help="hard per-request SLO for the interactive tier")
    ap.add_argument("--interactive-frac", type=float, default=0.5,
                    help="fraction of the stream submitted as "
                         "interactive-tier requests")
    ap.add_argument("--microbatch", type=int, default=256)
    ap.add_argument("--deadline-ms", type=float, default=2.0)
    ap.add_argument("--shards", type=int, default=0,
                    help="devices for shard_map serving (0 = unsharded)")
    ap.add_argument("--requests", type=int, default=2048)
    ap.add_argument("--rate", type=float, default=50_000.0)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-int8", action="store_true",
                    help="serve with the int8 KV cache")
    args = ap.parse_args()
    from repro.xla_env import use_compile_cache
    use_compile_cache()

    if args.lut:
        serve_lut(args)
        return

    cfg = R.get_config(args.arch, smoke=args.smoke)
    if R.is_encdec(cfg):
        raise SystemExit("use the encdec example for whisper serving")
    if args.kv_int8:
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")

    params = LM.init_params(jax.random.key(0), cfg)
    max_len = args.prompt_len + args.gen

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)

    prefill = jax.jit(lambda p, t: LM.prefill(p, cfg, t, max_len))
    serve = jax.jit(
        lambda p, c, t, pos: LM.decode_step(p, cfg, c, t, pos),
        donate_argnums=(1,))

    t0 = time.time()
    logits, cache = prefill(params, prompt)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    key = jax.random.key(1)
    tokens = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    t0 = time.time()
    for i in range(args.gen):
        tokens.append(tok)
        logits, cache = serve(params, cache, tok,
                              jnp.asarray(args.prompt_len + i, jnp.int32))
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits / args.temperature).astype(jnp.int32)[:, None]
        else:
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    out = jnp.concatenate(tokens, axis=1)
    tps = args.batch * args.gen / max(t_decode, 1e-9)
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill={t_prefill*1e3:.1f}ms "
          f"decode={t_decode*1e3:.1f}ms ({tps:.1f} tok/s) "
          f"first tokens={np.asarray(out[0, :8]).tolist()}")


if __name__ == "__main__":
    main()
