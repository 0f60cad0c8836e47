#!/usr/bin/env python3
"""Chip smoke test: serve the paper's JSC-XL-Add2 LUT network on a TPU.

One process drives the normal serving path once, at the model's
published width (16 inputs, widths 128-64-64-64-5, 5-bit codes, fan-in
2, adder width 2, 7-bit inputs):

  1. a few QAT steps on the chip (``core.lutdnn.make_train_step``);
  2. truth-table synthesis, ``save_artifact`` with its execution plan,
     and a packed ``load_artifact`` round trip;
  3. the artifact registered in a ``launch.registry.ModelRegistry``;
  4. single requests through its ``MicroBatcher`` (microbatch 256);
  5. every response checked against the plain ``jnp`` reference chain
     (``kernels/lut_gather/ref.py``), and the served program's compiled
     text checked for the Pallas kernel (``tpu_custom_call``).

``--four-chips`` runs only the sharded serving path instead: the same
network at B=4096 on a 4-device ``serving_mesh``, compared with the
single-device fused engine and the reference, with the output sharded
over the 4 devices.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --four-chips   # a four-chip host

The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
and is printed only when every phase passed.  Without a TPU the script
exits non-zero before doing anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
MICROBATCH = 256
N_REQUESTS = 2048
TRAIN_STEPS = 20
FOUR_CHIP_BATCH = 4096


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def reference_codes(tables, codes: np.ndarray) -> np.ndarray:
    """The plain jnp oracle chain (``ref.lut_layer``) on unpacked
    tables, run on the host CPU so it shares nothing with the kernel."""
    import jax

    from repro.kernels.lut_gather import ref

    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:              # the CPU backend is not loaded
        host = jax.devices()[0]
    with jax.default_device(host):
        out = jax.numpy.asarray(codes)
        for t in tables:
            out = ref.lut_layer(out, t.conn, t.sub_table, t.add_table,
                                t.in_bits, t.sub_bits)
        return np.asarray(out)


def input_codes(spec, n: int, seed: int = 0) -> np.ndarray:
    """``n`` quantised jsc test rows (sampled with replacement)."""
    from repro.data.loader import train_test_split
    from repro.data.synthetic import make_dataset

    data = train_test_split(make_dataset("jsc", n_samples=4000, seed=seed))
    x = np.asarray(data["test"]["x"])
    rows = x[np.random.default_rng(seed).integers(0, x.shape[0], n)]
    fq = spec.layer_specs()[0].in_quant
    return np.asarray(fq.to_code(fq.clip(rows))).astype(np.int32)


def train_and_synthesise(spec, steps: int, seed: int = 0):
    """``steps`` QAT steps on the jsc data, then synthesis.  Returns
    (tables, last loss)."""
    import jax

    from repro.core import lut_synth as LS
    from repro.core import lutdnn as LD
    from repro.data.loader import batch_iterator, train_test_split
    from repro.data.synthetic import make_dataset

    data = train_test_split(make_dataset("jsc", n_samples=4000, seed=seed))
    init_state, step = LD.make_train_step(spec, lr=5e-3)
    state = init_state(jax.random.key(seed))
    jstep = jax.jit(step)
    it = batch_iterator(data["train"], 256, seed=seed)
    loss = float("nan")
    for _ in range(steps):
        state, m = jstep(state, next(it))
        loss = float(m["loss"])
    return LS.synthesise(state["model"], spec), loss


def serve_phase(spec, tables, codes: np.ndarray, workdir: str, *,
                microbatch: int = MICROBATCH,
                force_interpret=None) -> dict:
    """Artifact round trip, then every row of ``codes`` as a single
    request through a ``ModelRegistry``'s batcher, checked against the
    reference chain.  Returns what the phase observed."""
    import jax
    import jax.numpy as jnp

    from repro.artifact import load_artifact, save_artifact
    from repro.kernels.lut_gather import ops
    from repro.launch.batching import latency_percentiles_ms
    from repro.launch.registry import ModelRegistry

    plan = ops.plan_segments(tables, n_in0=spec.in_features)
    path = save_artifact(workdir, tables, name="smoke", spec=spec,
                         plan=plan)
    art = load_artifact(path, unpack_int4=False)
    unpacked = load_artifact(path).tables
    round_trip = all(
        np.array_equal(np.asarray(getattr(a, k)), np.asarray(getattr(b, k)))
        for a, b in zip(tables, unpacked)
        for k in ("conn", "sub_table", "add_table"))
    _check(round_trip, "artifact round trip changed the tables")

    with ModelRegistry(microbatch, 2e-3,
                       force_interpret=force_interpret) as reg:
        entry = reg.register("smoke", path)
        compiled = entry.serve_fn.lower(
            jax.ShapeDtypeStruct((microbatch, spec.in_features),
                                 jnp.int32)).compile().as_text()
        t0 = time.monotonic()
        handles = [reg.submit("smoke", row) for row in codes]
        got = np.stack([h.result(timeout=300) for h in handles])
        span = time.monotonic() - t0
        flushes = len(entry.batcher.flushes)

    want = reference_codes(unpacked, codes)
    p50, p99 = latency_percentiles_ms(handles, qs=(50, 99))
    return {
        "artifact_id": art.artifact_id,
        "packed_load": any(t.sub_packed or t.add_packed
                           for t in art.tables),
        "plan": entry.plan.describe(),
        "warm_s": entry.warm_s,
        "kernel_in_program": "tpu_custom_call" in compiled,
        "served": sum(h.done and not h.failed for h in handles),
        "failed": sum(h.failed for h in handles),
        "mismatches": int(np.sum(np.any(got != want, axis=1))),
        "flushes": flushes,
        "span_s": span,
        "p50_ms": p50,
        "p99_ms": p99,
    }


def run_one_chip(spec, train_steps: int) -> None:
    t0 = time.monotonic()
    tables, loss = train_and_synthesise(spec, train_steps)
    _check(np.isfinite(loss), f"training loss is {loss}")
    print(f"train: {train_steps} QAT steps + synthesis in "
          f"{time.monotonic() - t0:.1f} s, last loss {loss:.4f}")
    codes = input_codes(spec, N_REQUESTS)
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
        r = serve_phase(spec, tables, codes, workdir)
    print(f"artifact: {r['artifact_id'][:12]} round-trips, packed load "
          f"int4={r['packed_load']}")
    print(f"execution_plan: {r['plan']}")
    print(f"compile+warm: {r['warm_s']:.2f} s "
          f"(trace, compile and one microbatch of {MICROBATCH})")
    print(f"tpu_custom_call in served program: {r['kernel_in_program']}")
    print(f"served {r['served']}/{N_REQUESTS} requests in {r['flushes']} "
          f"flushes, {r['failed']} failed, {r['mismatches']} mismatches "
          f"against ref.py")
    print(f"latency p50 {r['p50_ms']:.3f} ms / p99 {r['p99_ms']:.3f} ms "
          f"over {r['span_s']:.2f} s (smoke reading, not a metric)")
    _check(r["kernel_in_program"], "no Pallas kernel in the served program")
    _check(r["served"] == N_REQUESTS and r["failed"] == 0,
           "not every request was served")
    _check(r["mismatches"] == 0, "responses differ from ref.py")


def run_four_chips(spec) -> None:
    import jax

    from repro.core import lut_synth as LS
    from repro.core import lutdnn as LD
    from repro.kernels.lut_gather import ops
    from repro.parallel.sharding import serving_mesh

    _check(len(jax.devices()) >= 4,
           f"--four-chips needs 4 devices, found {len(jax.devices())}")
    tables = LS.synthesise(LD.init_model(jax.random.key(0), spec), spec)
    codes = input_codes(spec, FOUR_CHIP_BATCH)
    mesh = serving_mesh(4)
    sharded = ops.make_network_fn(tables, n_in0=spec.in_features, mesh=mesh)
    single = ops.make_network_fn(tables, n_in0=spec.in_features)
    print(f"execution_plan: {sharded.execution_plan.describe()}")
    t0 = time.monotonic()
    out = jax.block_until_ready(sharded(codes))
    print(f"sharded compile+run: {time.monotonic() - t0:.2f} s")
    n_dev = len(out.sharding.device_set)
    want_single = np.asarray(single(codes))
    want_ref = reference_codes(tables, codes)
    got = np.asarray(out)
    mism_single = int(np.sum(np.any(got != want_single, axis=1)))
    mism_ref = int(np.sum(np.any(got != want_ref, axis=1)))
    print(f"B={FOUR_CHIP_BATCH} on a {n_dev}-device serving_mesh: "
          f"{mism_single} rows differ from the single-device engine, "
          f"{mism_ref} from ref.py")
    _check(n_dev == 4, f"output spans {n_dev} devices, not 4")
    _check(mism_single == 0 and mism_ref == 0,
           "sharded output differs from single-device or ref.py")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 4-device serving path")
    args = ap.parse_args(argv)
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    try:
        import jax

        from repro.configs import paper_models as PM
        from repro.xla_env import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repository: {e}",
              file=sys.stderr)
        return 2
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend {backend!r})",
              file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}")
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    spec = PM.jsc_xl_add2()
    print(f"model: {spec.name} widths={spec.widths} bits={spec.bits} "
          f"fan_in={spec.fan_in} adder_width={spec.adder_width}")
    try:
        if args.four_chips:
            run_four_chips(spec)
        else:
            run_one_chip(spec, TRAIN_STEPS)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
