"""Shared harness of the cross-engine conformance tests
(``tests/test_conformance*.py``): ONE differential oracle for every LUT
inference engine.

Every engine is a pure execution-layout change of a synthesised LUT
network, so each must agree BIT-EXACTLY with the jnp reference chain
(kernels/lut_gather/ref.py) on the legacy int32 tables.
"""
import functools
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.kernels.lut_gather import ops as lg_ops, ref as lg_ref
from repro.parallel.sharding import serving_mesh


def oracle(tables, codes):
    for t in tables:
        codes = lg_ref.lut_layer(codes, t.conn, t.sub_table, t.add_table,
                                 t.in_bits, t.sub_bits)
    return np.asarray(codes)


@functools.lru_cache(maxsize=16)
def build(kw_items, seed=0):
    """(spec, packed tables, legacy int32 tables) for a ModelSpec given
    as sorted kwarg items; cached so a file synthesises each net once."""
    kw = dict(kw_items)
    spec = LD.ModelSpec(name="conf", **kw)
    model = LD.init_model(jax.random.key(seed), spec)
    return (spec, LS.synthesise(model, spec, pack=True),
            LS.synthesise(model, spec, pack=False))


def random_codes(spec, B, seed=9):
    return jax.random.randint(
        jax.random.key(seed), (B, spec.in_features), 0,
        2 ** spec.layer_specs()[0].in_quant.bits).astype(jnp.int32)


def forced_seg_plan(tables, block_b, n_in):
    """Plan with the budget shrunk until the planner has to cut —
    ``max(single-layer need, full/3)`` forces 2+ segments on any
    multi-layer net while staying feasible (every singleton fits)."""
    widths = [t.conn.shape[0] for t in tables]
    need = max(lg_ops.fused_vmem_bytes(
        tables[i:i + 1], block_b, n_in if i == 0 else widths[i - 1])
        for i in range(len(tables)))
    full = lg_ops.fused_vmem_bytes(tables, block_b, n_in)
    return lg_ops.plan_segments(tables, block_b=block_b, n_in0=n_in,
                                budget=max(need, full // 3 + 1),
                                prefer_int4=False)


def assert_conformant(kw: dict, B: int, block_b: int,
                      ndevs=(), artifact: bool = False):
    """Run the full engine matrix for one network draw and assert every
    engine matches the reference oracle bit-exactly."""
    spec, packed, legacy = build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    codes = random_codes(spec, B)
    want = oracle(legacy, codes)

    runs = {
        "per-layer-int32": lambda: lg_ops.lut_network(legacy, codes),
        "per-layer-uint8": lambda: lg_ops.lut_network(packed, codes),
        "per-layer-int4": lambda: lg_ops.lut_network(int4, codes),
        "fused-int32": lambda: lg_ops.lut_network_fused(
            legacy, codes, block_b=block_b),
        "fused-uint8": lambda: lg_ops.lut_network_fused(
            packed, codes, block_b=block_b),
        "fused-int4": lambda: lg_ops.lut_network_fused(
            int4, codes, block_b=block_b),
        "fused-uint8-pipelined": lambda: lg_ops.lut_network_fused(
            packed, codes, block_b=block_b, pipeline=True),
        "fused-int4-pipelined": lambda: lg_ops.lut_network_fused(
            int4, codes, block_b=block_b, pipeline=True),
    }
    # segmented engine: budget shrunk until the planner must cut (a
    # single-layer draw legitimately plans to 1 segment == fused)
    seg_plans = {"uint8": forced_seg_plan(packed, block_b,
                                          spec.in_features),
                 "int4": forced_seg_plan(int4, block_b,
                                         spec.in_features)}
    runs["segmented-uint8"] = functools.partial(
        lg_ops.lut_network_segmented, packed, codes, seg_plans["uint8"])
    runs["segmented-int4"] = functools.partial(
        lg_ops.lut_network_segmented, int4, codes, seg_plans["int4"])
    for nd in ndevs:
        if jax.device_count() < nd:
            continue
        runs[f"sharded-{nd}d-uint8"] = functools.partial(
            lg_ops.lut_network_fused_sharded, packed, codes,
            serving_mesh(nd), block_b)
        runs[f"sharded-{nd}d-int4"] = functools.partial(
            lg_ops.lut_network_fused_sharded, int4, codes,
            serving_mesh(nd), block_b)
        runs[f"sharded-{nd}d-segmented"] = functools.partial(
            lg_ops.lut_network_fused_sharded, packed, codes,
            serving_mesh(nd), plan=seg_plans["uint8"])

    tmp = tempfile.mkdtemp(prefix="lut-conf-") if artifact else None
    try:
        if artifact:
            from repro.artifact import load_artifact, save_artifact
            path = save_artifact(tmp, packed, spec=spec)
            art_u = load_artifact(path)
            art_p = load_artifact(path, unpack_int4=False)
            runs["artifact-unpacked"] = functools.partial(
                lg_ops.lut_network_fused, art_u.tables, codes, block_b)
            runs["artifact-packed"] = functools.partial(
                lg_ops.lut_network_fused, art_p.tables, codes, block_b)
        for name, fn in runs.items():
            got = np.asarray(fn())
            assert got.shape == want.shape, (name, got.shape)
            assert np.array_equal(got, want), \
                f"{name} diverges from oracle for {kw}, B={B}, " \
                f"block_b={block_b}"
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def random_draw(rng):
    """One random network draw (spec kwargs, B, block_b), kept
    kernel-sized: bits*fan_in <= 9 bounds K = 2**(bits*F),
    adder_width*(bits+1) <= 12 bounds Ka."""
    bits = int(rng.choice([1, 2, 3, 5]))
    fan_in = int(rng.integers(1, max(1, min(3, 9 // bits)) + 1))
    adder_width = int(rng.integers(
        1, max(1, min(3, 12 // (bits + 1))) + 1))
    n_hidden = int(rng.integers(0, 3))
    widths = tuple(int(rng.integers(4, 25)) for _ in range(n_hidden)) + \
        (int(rng.integers(3, 7)),)
    kw = dict(in_features=int(rng.integers(6, 17)), widths=widths,
              bits=bits, fan_in=fan_in, degree=int(rng.integers(1, 3)),
              adder_width=adder_width)
    return kw, int(rng.integers(1, 71)), \
        int(rng.choice([3, 8, 32, 256]))
