"""Compile-only checks of the LUT kernels for a described TPU v5e.

Nothing here runs on a chip: every case lowers an engine with
``force_interpret=False`` for a ``v5e:2x2`` topology that the installed
TPU compiler describes without hardware, compiles it, and asserts the
Pallas kernel (``tpu_custom_call``) is in the compiled program.  That
catches what interpret mode cannot — unsupported gathers, block shapes
off the (8, 128) tiling, scoped-VMEM overruns — at the paper's widths.

The topology and everything built from it live in module-scoped
fixtures: only the worker that runs this file loads the TPU compiler.
Tables are random arrays of the synthesised shapes and dtypes, passed
as abstract arguments, so no synthesis runs.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import paper_models as PM
from repro.core import lut_synth as LS
from repro.kernels.lut_gather import ops
from repro.kernels.lut_gather.lut_gather import (MATMUL_ROUTE_MAX_BITS,
                                                 routing_matrix)

B = 1024
# the benchmark's over-budget network: plans to 5 int4 fused segments
DEEPER_WIDER_3X = dict(in_features=16, widths=(512,) * 6 + (5,), bits=2,
                       fan_in=6, adder_width=2)


def _shaped_tables(spec, seed: int = 0):
    """Random LayerTables with the shapes and dtypes ``synthesise``
    emits for ``spec`` (codes kept inside each slab's bit width, so
    ``pack_tables_int4`` applies where synthesis output would)."""
    rng = np.random.default_rng(seed)
    out = []
    for s in spec.layer_specs():
        A, F, b_in = s.adder_width, s.fan_in, s.in_quant.bits
        conn = rng.integers(0, s.n_in, (s.n_out, A, F)).astype(np.int32)
        K = 2 ** (b_in * F)
        wide = 16 if s.is_output else s.out_quant.bits
        if A > 1:
            sub_bits = s.sub_quant.bits
            sub = rng.integers(0, 2 ** sub_bits, (s.n_out, A, K)).astype(
                LS.table_dtype_for(sub_bits))
            add = rng.integers(0, 2 ** min(wide, 15),
                               (s.n_out, 2 ** (A * sub_bits))).astype(
                LS.table_dtype_for(wide))
        else:
            sub_bits = s.out_quant.bits
            sub = rng.integers(0, 2 ** min(wide, 15), (s.n_out, 1, K)
                               ).astype(LS.table_dtype_for(wide))
            add = np.zeros((s.n_out, 0), sub.dtype)
        out.append(LS.LayerTables(
            conn=jnp.asarray(conn), sub_table=jnp.asarray(sub),
            add_table=jnp.asarray(add), in_bits=b_in, sub_bits=sub_bits,
            out_bits=s.out_quant.bits, fan_in=F, adder_width=A,
            is_output=s.is_output, out_quant=s.out_quant,
            sub_quant=s.sub_quant, table_dtype=jnp.dtype(sub.dtype),
            routing=(routing_matrix(conn, b_in, s.n_in)
                     if b_in * F <= MATMUL_ROUTE_MAX_BITS else None)))
    return out


def _abstract(tables, sharding):
    return [tuple(None if a is None else
                  jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
                  for a in (t.conn, t.sub_table, t.add_table, t.routing))
            for t in tables]


def _rebind(tables, arrays):
    return [dataclasses.replace(t, conn=c, sub_table=s, add_table=a,
                                routing=r)
            for t, (c, s, a, r) in zip(tables, arrays)]


def _compile_text(engine, tables, n_in, codes_sharding, table_sharding,
                  batch=B):
    """Compile ``engine(tables, codes)`` with the tables as abstract
    arguments; returns the compiled program's text."""
    codes = jax.ShapeDtypeStruct((batch, n_in), jnp.int32,
                                 sharding=codes_sharding)
    fn = jax.jit(lambda c, arrays: engine(_rebind(tables, arrays), c))
    return fn.lower(codes, _abstract(tables, table_sharding)
                    ).compile().as_text()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def jsc_tables():
    return _shaped_tables(PM.jsc_xl_add2())


@pytest.fixture(scope="module")
def hdr_tables():
    return _shaped_tables(PM.hdr_add2())


def test_fused_jsc_xl_add2(one_chip, jsc_tables):
    plan = ops.plan_segments(jsc_tables, n_in0=16)
    assert plan.mode == "fused" and not plan.pack_int4, plan.describe()
    text = _compile_text(
        lambda t, c: ops.lut_network_fused(t, c, block_b=B,
                                           force_interpret=False),
        jsc_tables, 16, one_chip, one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("int4", [False, True], ids=["uint8", "int4"])
def test_fused_hdr_add2(one_chip, hdr_tables, int4):
    tables = LS.pack_tables_int4(hdr_tables) if int4 else hdr_tables
    assert any(t.sub_packed for t in tables) == int4
    assert ops.plan_segments(tables, n_in0=784).mode == "fused"
    text = _compile_text(
        lambda t, c: ops.lut_network_fused(t, c, block_b=B,
                                           force_interpret=False),
        tables, 784, one_chip, one_chip)
    assert "tpu_custom_call" in text


def test_pipelined_multi_tile(one_chip, jsc_tables):
    text = _compile_text(
        lambda t, c: ops.lut_network_fused(t, c, block_b=B // 4,
                                           force_interpret=False,
                                           pipeline=True),
        jsc_tables, 16, one_chip, one_chip)
    assert "tpu_custom_call" in text


def test_per_layer(one_chip, jsc_tables):
    text = _compile_text(
        lambda t, c: ops.lut_network(t, c, force_interpret=False),
        jsc_tables, 16, one_chip, one_chip)
    assert text.count("tpu_custom_call") >= len(jsc_tables)


def test_segmented_deeper_wider_3x(one_chip):
    spec = PM.hdr_add2()
    spec = dataclasses.replace(spec, name="deeper-wider-3x",
                               **DEEPER_WIDER_3X)
    tables = _shaped_tables(spec)
    plan = ops.plan_segments(tables, n_in0=16)
    assert plan.mode == "segmented" and plan.n_segments == 5, \
        plan.describe()
    tables = LS.pack_tables_int4(tables) if plan.pack_int4 else tables
    text = _compile_text(
        lambda t, c: ops.lut_network_segmented(t, c, plan,
                                               force_interpret=False),
        tables, 16, one_chip, one_chip)
    assert text.count("tpu_custom_call") >= plan.n_segments


def test_sharded_four_devices(topo, jsc_tables):
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    plan = ops.plan_segments(jsc_tables, n_in0=16)
    text = _compile_text(
        lambda t, c: ops.lut_network_fused_sharded(
            t, c, mesh, force_interpret=False, plan=plan),
        jsc_tables, 16, NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P()), batch=4 * B)
    assert "tpu_custom_call" in text
