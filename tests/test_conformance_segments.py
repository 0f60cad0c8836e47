"""Segmented execution: nets over the (shrunken) VMEM budget run as a
chain of fused segments, bit-exact against the jnp oracle; and the
``plan_segments`` safety property — every segment it emits passes the
estimator it was planned under (estimate == actual == the plan's
recorded ledger, all within budget), and per-layer mode is chosen only
when some single layer genuinely cannot fit."""
import jax
import numpy as np
import pytest

from lut_conformance import (build, forced_seg_plan, oracle, random_codes,
                             random_draw)
from repro.core import lut_synth as LS
from repro.kernels.lut_gather import ops as lg_ops
from repro.parallel.sharding import serving_mesh


def test_segmented_forced_multi_segment_conformance():
    """The tentpole contract: a net whose slabs exceed the (shrunken)
    budget executes as 2-4 fused segments, bit-exact against the jnp
    oracle AND the per-layer path, across uint8/int4 slabs and sharded
    {1, 2, 4} devices."""
    kw = dict(in_features=16, widths=(40, 32, 24, 16, 5), bits=2,
              fan_in=3, degree=1, adder_width=2)
    spec, packed, legacy = build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    codes = random_codes(spec, 101)
    want = oracle(legacy, codes)
    assert np.array_equal(
        np.asarray(lg_ops.lut_network(packed, codes)), want)
    for nm, tbls in (("uint8", packed), ("int4", int4)):
        plan = forced_seg_plan(tbls, 64, spec.in_features)
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
    spec, packed, _ = build(tuple(sorted(kw.items())))
    plan = lg_ops.plan_segments(packed, block_b=64,
                                n_in0=spec.in_features)
    assert plan.mode == "fused" and plan.n_segments == 1, plan
    codes = random_codes(spec, 77)
    assert np.array_equal(
        np.asarray(lg_ops.lut_network_segmented(packed, codes, plan=plan)),
        np.asarray(lg_ops.lut_network_fused(packed, codes, block_b=64)))


def _plan_property(tables, n_in, block_b, budget):
    """The plan_segments safety property: every emitted segment passes
    the estimator it was planned under (estimate == independent actual
    == the plan's recorded ledger, all <= budget), the bounds partition
    the layer list, the batch tile shrinks below ``block_b`` only when
    some single layer busts the budget at twice the chosen tile, and
    per-layer mode is only ever chosen when some single layer cannot
    fit even at a 128-row tile."""
    plan = lg_ops.plan_segments(tables, block_b=block_b, n_in0=n_in,
                                budget=budget, prefer_int4=False)
    widths = [t.conn.shape[0] for t in tables]

    def most_needed(bb):
        return max(lg_ops.fused_vmem_bytes(
            tables[i:i + 1], bb, n_in if i == 0 else widths[i - 1])
            for i in range(len(tables)))

    if plan.mode == "per_layer":
        assert most_needed(128) > budget, (most_needed(128), budget)
        return plan
    tile = plan.block_b[0]
    assert set(plan.block_b) == {tile}
    if tile != block_b:
        assert tile % 128 == 0 and tile < block_b, (tile, block_b)
        assert most_needed(2 * tile) > budget, (tile, budget)
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


def _plan_draws(n):
    """n seeded (spec kwargs, block_b) draws."""
    rng = np.random.default_rng(3)
    draws = [random_draw(rng) for _ in range(n)]
    return [(kw, block_b) for kw, _, block_b in draws]


PLAN_DRAWS = _plan_draws(12)


@pytest.mark.parametrize("kw,block_b", PLAN_DRAWS,
                         ids=[f"draw{i}" for i in range(len(PLAN_DRAWS))])
def test_plan_segments_property_seeded(kw, block_b):
    """Random nets x a budget ladder (full // d for d in 1..9, and 1)
    through ``_plan_property`` on uint8 and int4 slabs, plus the
    degradation endpoint (full budget -> exactly 1 fused segment)."""
    spec, packed, _ = build(tuple(sorted(kw.items())))
    for tbls in (packed, LS.pack_tables_int4(packed)):
        full = lg_ops.fused_vmem_bytes(tbls, block_b, spec.in_features)
        for budget in sorted({max(1, full // d) for d in range(1, 10)}
                             | {1}):
            _plan_property(tbls, spec.in_features, block_b, budget)
    plan = lg_ops.plan_segments(packed, block_b=block_b,
                                n_in0=spec.in_features,
                                budget=lg_ops.fused_vmem_bytes(
                                    packed, block_b, spec.in_features))
    assert plan.mode == "fused" and plan.n_segments == 1
