"""Cross-engine conformance: ONE differential oracle for every LUT
inference engine.

Seven execution paths now exist for a synthesised LUT network —
per-layer Pallas (packed uint8 / legacy int32 / int4 nibble-packed),
fused single-kernel (same three layouts, grid-mode or double-buffered
pipeline), SEGMENTED (a cost-model plan chaining 2+ fused kernels with
inter-segment codes staged through HBM — forced here by shrinking the
planner's budget), shard_map data-parallel over {1, 2, 4} devices, and
the artifact round-trip (save -> content-addressed load, unpacked or
packed).  Every one of them is a pure execution-layout change, so they
must agree BIT-EXACTLY with the jnp reference chain
(kernels/lut_gather/ref.py) on the legacy int32 tables.

This harness replaces ad-hoc per-engine exactness tests as the single
oracle: a hypothesis fuzz draws random network specs (layer widths,
fan-in, code bits spanning int4 / uint8 / int32 slabs, adder on/off,
polynomial degree, remainder batch sizes, ragged block_b) and runs the
WHOLE engine matrix against the oracle; a deterministic sweep pins the
corner cases (adder-off through the packed kernel, single-row batches,
block_b larger than B) so coverage survives environments without
hypothesis.  The long fuzz variant is ``@pytest.mark.slow`` — the fast
tier-1 lane runs the short one.

Also here: the ``fused_vmem_bytes`` accounting property — the analytic
fusion-eligibility estimate is pinned against the ACTUAL flattened
slab + scratch allocation (``ops.fused_vmem_actual``) for packed and
unpacked layouts, pipelined and grid tiles, so the estimator cannot
silently drift from what the kernel binds — and its segmented
extension: every segment ``plan_segments`` emits must pass the
estimator it was planned under (estimate == actual == the plan's
recorded ledger, all within budget), and per-layer mode may only be
chosen when some single layer genuinely cannot fit.
"""
import functools
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.kernels.lut_gather import ops as lg_ops, ref as lg_ref
from repro.parallel.sharding import serving_mesh

try:                      # fuzz rides hypothesis when present; the
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # deterministic sweep below runs regardless
    HAVE_HYPOTHESIS = False


def _oracle(tables, codes):
    for t in tables:
        codes = lg_ref.lut_layer(codes, t.conn, t.sub_table, t.add_table,
                                 t.in_bits, t.sub_bits)
    return np.asarray(codes)


@functools.lru_cache(maxsize=16)
def _build(kw_items, seed=0):
    kw = dict(kw_items)
    spec = LD.ModelSpec(name="conf", **kw)
    model = LD.init_model(jax.random.key(seed), spec)
    return (spec, LS.synthesise(model, spec, pack=True),
            LS.synthesise(model, spec, pack=False))


def _codes(spec, B, seed=9):
    return jax.random.randint(
        jax.random.key(seed), (B, spec.in_features), 0,
        2 ** spec.layer_specs()[0].in_quant.bits).astype(jnp.int32)


def _forced_seg_plan(tables, block_b, n_in):
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


def _assert_conformant(kw: dict, B: int, block_b: int,
                       ndevs=(), artifact: bool = False):
    """Run the full engine matrix for one network draw and assert every
    engine matches the reference oracle bit-exactly."""
    spec, packed, legacy = _build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    codes = _codes(spec, B)
    want = _oracle(legacy, codes)

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
    seg_plans = {"uint8": _forced_seg_plan(packed, block_b,
                                           spec.in_features),
                 "int4": _forced_seg_plan(int4, block_b,
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


# ---------------------------------------------------------------------------
# deterministic corner sweep (always runs, hypothesis or not)
# ---------------------------------------------------------------------------

CORNERS = [
    # (name, spec kwargs, B, block_b) — chosen to pin: adder OFF through
    # the packed/pipelined kernels (the dummy add-table binding), A=3,
    # bits=1 (every slab int4-eligible), bits=5/fan_in=1 (uint8 codes
    # too wide to nibble-pack), B=1, B < block_b, block_b that leaves a
    # remainder tile, and a 4-deep network.
    ("adder-off-int4", dict(in_features=16, widths=(12, 5), bits=2,
                            fan_in=3, degree=1, adder_width=1), 40, 8),
    ("adder-off-deep", dict(in_features=16, widths=(16, 12, 5), bits=2,
                            fan_in=2, degree=2, adder_width=1), 33, 256),
    ("adder3", dict(in_features=10, widths=(33, 5), bits=2, fan_in=2,
                    degree=1, adder_width=3), 7, 3),
    ("bits1", dict(in_features=8, widths=(9, 4), bits=1, fan_in=3,
                   degree=1, adder_width=2), 1, 8),
    ("bits5-uint8", dict(in_features=6, widths=(7, 4), bits=5, fan_in=1,
                         degree=1, adder_width=2), 21, 32),
    ("deep4", dict(in_features=16, widths=(40, 24, 16, 5), bits=2,
                   fan_in=3, degree=1, adder_width=2), 257, 64),
    # 33 batch tiles of 128 lanes: past PIPELINE_UNROLL_MAX_TILES, so
    # the pipelined engine takes the ROLLED fori_loop path (dynamic
    # buffer slots)
    ("pipeline-rolled", dict(in_features=10, widths=(8, 4), bits=2,
                             fan_in=2, degree=1, adder_width=2), 4097, 128),
]


@pytest.mark.parametrize("name,kw,B,block_b", CORNERS,
                         ids=[c[0] for c in CORNERS])
def test_conformance_corners(name, kw, B, block_b):
    _assert_conformant(kw, B, block_b, ndevs=(1, 2, 4),
                       artifact=(name == "deep4"))


def test_adder_off_through_packed_kernel():
    """Regression for the zero-width add-table binding: an adder-off
    layer's dummy must never be read or treated as packed — the
    per-layer kernel accepts add_packed=True with an EMPTY add table
    and stays exact (the flag is forced off with use_adder)."""
    kw = dict(in_features=16, widths=(12, 5), bits=2, fan_in=3,
              degree=1, adder_width=1)
    spec, packed, legacy = _build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    assert all(t.add_table.shape[-1] == 0 for t in int4)
    assert any(t.sub_packed for t in int4)
    codes = _codes(spec, 19)
    want = _oracle(legacy, codes)
    out = codes
    for t in int4:
        out = lg_ops.lut_layer(out, t.conn, t.sub_table, t.add_table,
                               t.in_bits, t.sub_bits,
                               sub_packed=t.sub_packed,
                               add_packed=True)   # hostile flag: no-op
    assert np.array_equal(np.asarray(out), want)


# ---------------------------------------------------------------------------
# fuzz sweep: hypothesis when present, a seeded random stand-in always
# ---------------------------------------------------------------------------

def _random_draw(rng):
    """One random network draw under the same bounds as the hypothesis
    strategy: bits*fan_in <= 9 bounds K, adder_width*(bits+1) <= 12
    bounds Ka."""
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


def test_conformance_random_sweep():
    """Seeded stand-in for the hypothesis fuzz (always runs, with or
    without hypothesis): random draws through the full engine matrix."""
    rng = np.random.default_rng(7)
    for _ in range(4):
        kw, B, block_b = _random_draw(rng)
        _assert_conformant(kw, B, block_b, ndevs=(2,))


@pytest.mark.slow
def test_conformance_random_sweep_long():
    """The long fuzz: more draws, all device counts, artifact
    round-trip per draw."""
    rng = np.random.default_rng(11)
    for _ in range(12):
        kw, B, block_b = _random_draw(rng)
        _assert_conformant(kw, B, block_b, ndevs=(1, 2, 4),
                           artifact=True)


if HAVE_HYPOTHESIS:
    @st.composite
    def _net_draws(draw):
        # keep table enumeration kernel-sized: bits*fan_in <= 9 bounds
        # K = 2**(bits*F), adder_width*(bits+1) <= 12 bounds Ka
        bits = draw(st.sampled_from([1, 2, 3, 5]))
        fan_in = draw(st.integers(1, max(1, min(3, 9 // bits))))
        adder_width = draw(st.integers(
            1, max(1, min(3, 12 // (bits + 1)))))
        n_hidden = draw(st.integers(0, 2))
        widths = tuple(draw(st.integers(4, 24))
                       for _ in range(n_hidden)) + \
            (draw(st.integers(3, 6)),)
        kw = dict(in_features=draw(st.integers(6, 16)), widths=widths,
                  bits=bits, fan_in=fan_in,
                  degree=draw(st.integers(1, 2)),
                  adder_width=adder_width)
        return kw, draw(st.integers(1, 70)), \
            draw(st.sampled_from([3, 8, 32, 256]))

    @settings(max_examples=6, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(_net_draws())
    def test_conformance_fuzz(draw):
        kw, B, block_b = draw
        _assert_conformant(kw, B, block_b, ndevs=(2,))

    @pytest.mark.slow
    @settings(max_examples=30, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(_net_draws())
    def test_conformance_fuzz_long(draw):
        kw, B, block_b = draw
        _assert_conformant(kw, B, block_b, ndevs=(1, 2, 4),
                           artifact=True)


# ---------------------------------------------------------------------------
# VMEM accounting: the fusion-eligibility estimate equals the kernel's
# actual allocation
# ---------------------------------------------------------------------------

VMEM_NETS = [
    ("adder", dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
                   degree=1, adder_width=2)),
    ("adder-off", dict(in_features=16, widths=(12, 5), bits=2, fan_in=3,
                       degree=1, adder_width=1)),
    ("bits3", dict(in_features=12, widths=(9, 5), bits=3, fan_in=3,
                   degree=1, adder_width=2)),
]


@pytest.mark.parametrize("name,kw", VMEM_NETS, ids=[n[0] for n in VMEM_NETS])
@pytest.mark.parametrize("layout", ["uint8", "int4", "int32"])
@pytest.mark.parametrize("pipeline", [False, True],
                         ids=["grid", "pipelined"])
def test_fused_vmem_estimate_matches_actual(name, kw, layout, pipeline):
    spec, packed, legacy = _build(tuple(sorted(kw.items())))
    tables = {"uint8": packed, "int32": legacy,
              "int4": LS.pack_tables_int4(packed)}[layout]
    for block_b in (8, 256, 1024):
        est = lg_ops.fused_vmem_bytes(tables, block_b,
                                      spec.in_features, pipeline)
        act = lg_ops.fused_vmem_actual(tables, block_b,
                                       spec.in_features, pipeline)
        assert est == act, (name, layout, pipeline, block_b, est, act)
    # the pipeline's double-buffered tiles cost more than grid mode's
    assert lg_ops.fused_vmem_bytes(tables, 256, spec.in_features, True) > \
        lg_ops.fused_vmem_bytes(tables, 256, spec.in_features, False)


def test_int4_residency_halved():
    """For a 4-bit-code network (every hidden slab nibble-packable) the
    packed table residency lands at <= 0.55x the uint8 layout — the
    int32 logit tail of the output layer is all that stays wide — and
    the fused VMEM estimate drops accordingly, raising the can_fuse
    ceiling."""
    kw = dict(in_features=16, widths=(64, 32, 32, 32, 5), bits=2,
              fan_in=3, degree=1, adder_width=2)
    spec, packed, _ = _build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    u8 = sum(t.table_bytes for t in packed)
    i4 = sum(t.table_bytes for t in int4)
    assert i4 <= 0.55 * u8, (i4, u8)
    assert lg_ops.fused_vmem_bytes(int4, 256, spec.in_features) < \
        lg_ops.fused_vmem_bytes(packed, 256, spec.in_features)


def test_tune_block_b_never_probes_over_budget(monkeypatch):
    """An over-budget network must not execute a fused timing probe (on
    real TPU that can OOM at serving start): tune_block_b raises, and
    make_network_fn(block_b="auto") silently routes to the per-layer
    engine instead of sweeping."""
    kw = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
              degree=1, adder_width=2)
    spec, packed, _ = _build(tuple(sorted(kw.items())))
    monkeypatch.setattr(lg_ops, "FUSED_VMEM_BUDGET_BYTES", 1)
    with pytest.raises(ValueError, match="per-layer"):
        lg_ops.tune_block_b(packed, batch=64)
    probes = []
    monkeypatch.setattr(
        lg_ops, "tune_block_b",
        lambda *a, **k: probes.append(1) or (64, {64: 1.0}))
    fn = lg_ops.make_network_fn(packed, block_b="auto", tune_batch=64)
    assert probes == []                     # no sweep when not fusing
    codes = _codes(spec, 48)
    assert np.array_equal(np.asarray(fn(codes)), _oracle(packed, codes))


def test_save_artifact_int4_false_expands_packed_tables(tmp_path):
    """int4=False promises raw slabs everywhere, even when handed
    already-packed tables: the slab bytes (and artifact id) must match
    a raw save from unpacked tables, and the default load must see no
    packed flags."""
    from repro.artifact import load_artifact, save_artifact
    kw = dict(in_features=16, widths=(12, 7, 5), bits=2, fan_in=3,
              degree=2, adder_width=2)
    spec, packed, _ = _build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    p_raw = save_artifact(str(tmp_path / "a"), packed, int4=False)
    p_from_packed = save_artifact(str(tmp_path / "b"), int4, int4=False)
    assert p_raw.split("-")[-1] == p_from_packed.split("-")[-1]
    art = load_artifact(p_from_packed)
    assert all(s["encoding"] == "raw" for s in art.manifest["slabs"])
    assert not any(t.sub_packed or t.add_packed for t in art.tables)
    codes = _codes(spec, 23)
    assert np.array_equal(
        np.asarray(lg_ops.lut_network_fused(art.tables, codes)),
        _oracle(packed, codes))


def test_segmented_forced_multi_segment_conformance():
    """The tentpole contract: a net whose slabs exceed the (shrunken)
    budget executes as 2-4 fused segments, bit-exact against the jnp
    oracle AND the per-layer path, across uint8/int4 slabs and sharded
    {1, 2, 4} devices."""
    kw = dict(in_features=16, widths=(40, 32, 24, 16, 5), bits=2,
              fan_in=3, degree=1, adder_width=2)
    spec, packed, legacy = _build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    codes = _codes(spec, 101)
    want = _oracle(legacy, codes)
    assert np.array_equal(
        np.asarray(lg_ops.lut_network(packed, codes)), want)
    for nm, tbls in (("uint8", packed), ("int4", int4)):
        plan = _forced_seg_plan(tbls, 64, spec.in_features)
        assert plan.mode == "segmented", (nm, plan)
        assert 2 <= plan.n_segments <= 4, (nm, plan)
        got = np.asarray(lg_ops.lut_network_segmented(
            tbls, codes, plan=plan))
        assert np.array_equal(got, want), nm
        for nd in (1, 2, 4):
            if jax.device_count() < nd:
                continue
            out = np.asarray(lg_ops.lut_network_fused_sharded(
                tbls, codes, serving_mesh(nd), plan=plan))
            assert np.array_equal(out, want), (nm, nd)


def test_segmented_one_segment_is_exact_fused_path():
    """Degradation contract: a net that fits the budget plans to
    exactly ONE segment, and executing that plan is bit-identical to
    the classic fully fused call."""
    kw = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
              degree=1, adder_width=2)
    spec, packed, _ = _build(tuple(sorted(kw.items())))
    plan = lg_ops.plan_segments(packed, block_b=64,
                                n_in0=spec.in_features)
    assert plan.mode == "fused" and plan.n_segments == 1, plan
    codes = _codes(spec, 77)
    assert np.array_equal(
        np.asarray(lg_ops.lut_network_segmented(packed, codes, plan=plan)),
        np.asarray(lg_ops.lut_network_fused(packed, codes, block_b=64)))


def _plan_property(tables, n_in, block_b, budget):
    """The plan_segments safety property: every emitted segment passes
    the estimator it was planned under (estimate == independent actual
    == the plan's recorded ledger, all <= budget), the bounds partition
    the layer list, and per-layer mode is only ever chosen when some
    single layer genuinely cannot fit."""
    plan = lg_ops.plan_segments(tables, block_b=block_b, n_in0=n_in,
                                budget=budget, prefer_int4=False)
    widths = [t.conn.shape[0] for t in tables]
    if plan.mode == "per_layer":
        needs = [lg_ops.fused_vmem_bytes(
            tables[i:i + 1], block_b, n_in if i == 0 else widths[i - 1])
            for i in range(len(tables))]
        assert max(needs) > budget, (needs, budget)
        return plan
    assert plan.bounds[0][0] == 0 and plan.bounds[-1][1] == len(tables)
    for (a, b), (c, d) in zip(plan.bounds, plan.bounds[1:]):
        assert b == c and a < b
    assert plan.bounds[-1][0] < plan.bounds[-1][1]
    for (s, e), bb, v in zip(plan.bounds, plan.block_b, plan.vmem_bytes):
        seg_in = n_in if s == 0 else widths[s - 1]
        est = lg_ops.fused_vmem_bytes(tables[s:e], bb, seg_in,
                                      plan.pipeline)
        act = lg_ops.fused_vmem_actual(tables[s:e], bb, seg_in,
                                       plan.pipeline)
        assert est == act == v, (s, e, est, act, v)
        assert v <= budget, (s, e, v, budget)
    assert plan.cut_widths == tuple(
        widths[e - 1] for _, e in plan.bounds[:-1])
    return plan


def test_plan_segments_property_seeded():
    """Seeded stand-in for the hypothesis property fuzz (always runs):
    random nets x budget ladders through ``_plan_property``, plus the
    degradation endpoints (full budget -> exactly 1 fused segment)."""
    rng = np.random.default_rng(3)
    for _ in range(5):
        kw, _, block_b = _random_draw(rng)
        spec, packed, _ = _build(tuple(sorted(kw.items())))
        for tbls in (packed, LS.pack_tables_int4(packed)):
            full = lg_ops.fused_vmem_bytes(tbls, block_b,
                                           spec.in_features)
            for budget in (full, full // 2, full // 4, 1):
                _plan_property(tbls, spec.in_features, block_b,
                               max(budget, 1))
        plan = lg_ops.plan_segments(packed, block_b=block_b,
                                    n_in0=spec.in_features,
                                    budget=lg_ops.fused_vmem_bytes(
                                        packed, block_b,
                                        spec.in_features))
        assert plan.mode == "fused" and plan.n_segments == 1


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(_net_draws(), st.integers(1, 9))
    def test_plan_segments_property_fuzz(draw, denom):
        kw, _, block_b = draw
        spec, packed, _ = _build(tuple(sorted(kw.items())))
        full = lg_ops.fused_vmem_bytes(packed, block_b,
                                       spec.in_features)
        _plan_property(packed, spec.in_features, block_b,
                       max(1, full // denom))


def test_tune_block_b_returns_valid_candidate():
    kw = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
              degree=1, adder_width=2)
    spec, packed, _ = _build(tuple(sorted(kw.items())))
    best, timings = lg_ops.tune_block_b(packed, batch=64,
                                        candidates=(16, 32, 64, 256),
                                        iters=1)
    assert best in timings and timings
    assert all(bb <= 64 for bb in timings)          # clamped to batch
    assert all(t > 0 for t in timings.values())
    # "auto" wires the sweep into the serving entry
    fn = lg_ops.make_network_fn(packed, block_b="auto", tune_batch=64)
    codes = _codes(spec, 48)
    assert np.array_equal(np.asarray(fn(codes)),
                          _oracle(packed, codes))
