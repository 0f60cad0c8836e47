"""Wide first layers: CIFAR-HDR-Add2 (3,072 inputs) on the fused path.

A 3,072-wide first layer's routing matrix (6 MiB of float32) and its
(3,072, TB) input tile bust the fused VMEM budget at a 1,024-row batch
tile, though the whole network fits at 256.  ``plan_segments`` then
halves the tile, in whole 128-row groups, before it falls back to the
per-layer engine; a network that plans at the requested tile keeps the
plan it had.  The served network is checked bit-exact against the jnp
reference through the artifact store and through the registry.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lut_conformance import build, oracle
from paper_tables import DEEPER_WIDER_3X, shaped_tables
from repro.artifact import load_artifact, save_artifact
from repro.configs import paper_models as PM
from repro.core import lut_synth as LS
from repro.kernels.lut_gather import ops as lg_ops
from repro.launch.registry import ModelRegistry

CIFAR_IN = 3072
ROWS = 256


def _summary(n_in0, mode, pipeline, pack_int4, segs):
    """A ``SegmentPlan.summary()`` at the default budget; ``segs`` are
    (first layer, end layer, block_b, vmem_bytes, n_in, n_out)."""
    return dict(
        mode=mode, n_segments=len(segs), n_in0=n_in0,
        budget_bytes=lg_ops.FUSED_VMEM_BUDGET_BYTES, pipeline=pipeline,
        pack_int4=pack_int4, block_b_tuned=[s[2] for s in segs],
        cut_widths=[s[5] for s in segs[:-1]],
        segments=[dict(layers=[s[0], s[1]], block_b=s[2], vmem_bytes=s[3],
                       n_in=s[4], n_out=s[5]) for s in segs])


# plans of networks the planner fitted at block_b 1024 before it could
# shrink the tile, as it gave them then (unpacked tables, as the
# benchmark's artifacts are saved)
KEPT_PLANS = {
    "jsc-xl-add2": (PM.jsc_xl_add2, _summary(
        16, "fused", False, False, [(0, 5, 1024, 2589184, 16, 5)])),
    "hdr-add2": (PM.hdr_add2, _summary(
        784, "fused", False, False, [(0, 6, 1024, 6744768, 784, 10)])),
    "deeper-wider-3x": (
        lambda: dataclasses.replace(PM.hdr_add2(), name="deeper-wider-3x",
                                    **DEEPER_WIDER_3X),
        _summary(16, "segmented", False, True,
                 [(0, 2, 1024, 10649600, 16, 512)]
                 + [(i, i + 1, 1024, 8409088, 512, 512) for i in (2, 3, 4)]
                 + [(5, 7, 1024, 8467712, 512, 5)])),
}


@pytest.mark.parametrize("name", sorted(KEPT_PLANS))
def test_plan_kept_where_requested_tile_fits(name):
    spec_fn, want = KEPT_PLANS[name]
    spec = spec_fn()
    plan = lg_ops.plan_segments(shaped_tables(spec),
                                n_in0=spec.in_features)
    assert plan.summary() == want


def _needs(tables, n_in, block_b):
    """Each single layer's fused VMEM claim at ``block_b``."""
    widths = [t.conn.shape[0] for t in tables]
    return [lg_ops.fused_vmem_bytes(tables[i:i + 1], block_b,
                                    n_in if i == 0 else widths[i - 1])
            for i in range(len(tables))]


@pytest.mark.parametrize("fit_tile,want_tile", [
    (512, 512), (256, 256), (128, 128), (384, 256)])
def test_planner_shrinks_tile_only_where_a_layer_busts(fit_tile, want_tile):
    """A small network with a wide first layer under a budget equal to
    its whole claim at ``fit_tile``: the first layer busts at 1024, the
    planner halves the tile (whole 128-row groups) and plans one fused
    segment at the largest halving that fits; one byte less at 128 is
    the per-layer fallback."""
    kw = dict(in_features=600, widths=(64, 10), bits=2, fan_in=3,
              degree=1, adder_width=2)
    spec, tables, _ = build(tuple(sorted(kw.items())))
    n_in = spec.in_features
    budget = lg_ops.fused_vmem_bytes(tables, fit_tile, n_in)
    assert max(_needs(tables, n_in, 1024)) > budget
    plan = lg_ops.plan_segments(tables, block_b=1024, n_in0=n_in,
                                budget=budget, prefer_int4=False)
    assert plan.mode == "fused" and plan.block_b == (want_tile,), \
        plan.describe()
    assert plan.vmem_bytes == (
        lg_ops.fused_vmem_actual(tables, want_tile, n_in),)
    assert plan.vmem_bytes[0] <= budget
    assert f"block_b={want_tile}" in plan.describe()
    # at the requested tile the network keeps its plan
    assert lg_ops.plan_segments(
        tables, block_b=want_tile, n_in0=n_in, budget=budget,
        prefer_int4=False) == plan
    tight = max(_needs(tables, n_in, 128)) - 1
    assert lg_ops.plan_segments(tables, block_b=1024, n_in0=n_in,
                                budget=tight,
                                prefer_int4=False).mode == "per_layer"


@pytest.fixture(scope="module")
def cifar():
    spec = PM.cifar_hdr_add2()
    return spec, shaped_tables(spec, seed=11)


def test_cifar_hdr_add2_plans_one_fused_segment(cifar):
    spec, tables = cifar
    assert spec.in_features == CIFAR_IN
    assert max(_needs(tables, CIFAR_IN, 1024)) > \
        lg_ops.FUSED_VMEM_BUDGET_BYTES
    for packed in (False, True):
        tbls = LS.pack_tables_int4(tables) if packed else tables
        plan = lg_ops.plan_segments(tbls, n_in0=CIFAR_IN)
        assert plan.mode == "fused" and plan.n_segments == 1, \
            plan.describe()
        assert plan.block_b[0] <= 256
        assert plan.vmem_bytes[0] == lg_ops.fused_vmem_actual(
            tbls, plan.block_b[0], CIFAR_IN)
        assert plan.vmem_bytes[0] <= 12 * 2 ** 20


@pytest.mark.parametrize("spec_fn,macs", [
    (PM.cifar_hdr_add2, 1_686_064), (PM.hdr_add2, 514_608),
    (PM.jsc_xl_add2, 37_504)], ids=["cifar-hdr-add2", "hdr-add2",
                                    "jsc-xl-add2"])
def test_routing_macs_per_row(spec_fn, macs):
    spec = spec_fn()
    fn = lg_ops.make_network_fn(shaped_tables(spec),
                                n_in0=spec.in_features)
    assert fn.routing_macs_per_row == macs
    assert lg_ops.make_network_fn(shaped_tables(spec), fused=False,
                                  n_in0=spec.in_features
                                  ).routing_macs_per_row == 0


@pytest.fixture(scope="module")
def cifar_artifact(cifar, tmp_path_factory):
    spec, tables = cifar
    plan = lg_ops.plan_segments(tables, n_in0=CIFAR_IN)
    path = save_artifact(str(tmp_path_factory.mktemp("cifar")), tables,
                         name="cifar-hdr-add2", spec=spec, plan=plan)
    codes = jax.random.randint(jax.random.key(4), (ROWS, CIFAR_IN), 0,
                               2 ** spec.layer_specs()[0].in_quant.bits,
                               dtype=jnp.int32)
    return path, codes, oracle(tables, codes)


def test_cifar_artifact_engine_exact(cifar_artifact):
    path, codes, want = cifar_artifact
    fn = lg_ops.make_network_fn(load_artifact(path, unpack_int4=False))
    assert fn.execution_plan.mode == "fused"
    assert fn.execution_plan.block_b == (256,)
    assert np.array_equal(np.asarray(fn(codes)), want)


def test_cifar_registry_stream_exact(cifar_artifact):
    path, codes, want = cifar_artifact
    with ModelRegistry(microbatch=128, deadline_s=5e-3) as reg:
        reg.register("cifar", path)
        st = reg.stats()["cifar"]
        assert (st["exec_mode"], st["exec_segments"],
                st["exec_block_b"]) == ("fused", 1, [256])
        handles = [reg.submit("cifar", r) for r in np.asarray(codes)]
        got = np.stack([h.result(timeout=60.0) for h in handles])
    assert np.array_equal(got, want)
