"""Bit-exactness of the fused LUT engine and packed uint8 tables.

Contract: for any synthesised network, the fused single-kernel path and
the per-layer Pallas path — with packed (uint8) or legacy (int32)
tables, matmul or gather routing — all agree EXACTLY with the
kernels/lut_gather/ref.py jnp oracle chained layer by layer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from paper_tables import shaped_tables
from repro.configs import paper_models as PM
from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.kernels.lut_gather import ops as lg_ops, ref as lg_ref
from repro.kernels.lut_gather.lut_gather import (LANES, _lookup,
                                                 lookup_slab,
                                                 routing_matrix, word_packs)


def _ref_chain(tables, codes):
    for t in tables:
        codes = lg_ref.lut_layer(codes, t.conn, t.sub_table, t.add_table,
                                 t.in_bits, t.sub_bits)
    return codes


def _synth(spec, seed=0, pack=True):
    model = LD.init_model(jax.random.key(seed), spec)
    return LS.synthesise(model, spec, pack=pack)


def _codes(spec, B, seed=9):
    return jax.random.randint(
        jax.random.key(seed), (B, spec.in_features), 0,
        2 ** spec.layer_specs()[0].in_quant.bits).astype(jnp.int32)


NETS = [
    # (name, spec kwargs, batch) — ragged batch/neuron sizes on purpose
    ("A1-no-adder", dict(in_features=16, widths=(12, 5), bits=2,
                         fan_in=3, degree=1, adder_width=1), 40),
    ("A2-adder", dict(in_features=16, widths=(12, 7, 5), bits=2,
                      fan_in=3, degree=2, adder_width=2), 41),
    ("A3-adder", dict(in_features=10, widths=(33, 5), bits=2,
                      fan_in=2, degree=1, adder_width=3), 7),
    ("deep", dict(in_features=16, widths=(40, 24, 16, 5), bits=2,
                  fan_in=3, degree=1, adder_width=2), 257),
    ("b3-wideK", dict(in_features=12, widths=(9, 5), bits=3,
                      fan_in=3, degree=1, adder_width=2), 33),
]


@pytest.mark.parametrize("name,kw,B", NETS, ids=[n[0] for n in NETS])
@pytest.mark.parametrize("pack", [True, False], ids=["uint8", "int32"])
def test_fused_matches_ref_chain(name, kw, B, pack):
    spec = LD.ModelSpec(name=name, **kw)
    tables = _synth(spec, pack=pack)
    if pack:
        # hidden layers pack to uint8; the output layer's logit-code
        # table (sub when A=1, add when A>1) stays int32
        assert all(t.sub_table.dtype == jnp.uint8
                   for t in tables if not t.is_output)
        out = tables[-1]
        wide = out.sub_table if out.adder_width == 1 else out.add_table
        assert wide.dtype == jnp.int32
        assert all(t.table_dtype == t.sub_table.dtype for t in tables)
    else:
        assert all(t.sub_table.dtype == jnp.int32 for t in tables)
    codes = _codes(spec, B)
    want = _ref_chain(tables, codes)
    got = lg_ops.lut_network_fused(tables, codes)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name,kw,B", NETS, ids=[n[0] for n in NETS])
def test_per_layer_packed_matches_ref_chain(name, kw, B):
    spec = LD.ModelSpec(name=name, **kw)
    tables = _synth(spec, pack=True)
    codes = _codes(spec, B)
    want = _ref_chain(tables, codes)
    got = lg_ops.lut_network(tables, codes)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_packed_and_int32_tables_agree():
    """pack=True only narrows storage — identical codes, 4x smaller."""
    spec = LD.ModelSpec(name="t", in_features=16, widths=(12, 7, 5),
                        bits=2, fan_in=3, degree=2, adder_width=2)
    model = LD.init_model(jax.random.key(1), spec)
    packed = LS.synthesise(model, spec, pack=True)
    legacy = LS.synthesise(model, spec, pack=False)
    for tp, ti in zip(packed, legacy):
        assert np.array_equal(np.asarray(tp.sub_table, dtype=np.int64),
                              np.asarray(ti.sub_table, dtype=np.int64))
        assert np.array_equal(np.asarray(tp.add_table, dtype=np.int64),
                              np.asarray(ti.add_table, dtype=np.int64))
    assert (LS.network_table_bytes(packed)
            < LS.network_table_bytes(legacy))
    codes = _codes(spec, 64)
    a = lg_ops.lut_network_fused(packed, codes)
    b = lg_ops.lut_network_fused(legacy, codes)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_output_layer_tables_stay_wide():
    """16-bit logit codes cannot be packed into uint8."""
    spec = LD.ModelSpec(name="t", in_features=16, widths=(12, 5), bits=2,
                        fan_in=3, degree=1, adder_width=2)
    tables = _synth(spec)
    assert tables[-1].is_output
    assert tables[-1].add_table.dtype == jnp.int32   # adder emits logits
    assert tables[-1].sub_table.dtype == jnp.uint8   # sub codes still fit


def test_fused_batch_tile_padding():
    """Batch sizes that do not divide block_b are padded and sliced."""
    spec = LD.ModelSpec(name="t", in_features=16, widths=(12, 5), bits=2,
                        fan_in=3, degree=1, adder_width=2)
    tables = _synth(spec)
    for B, block_b in [(5, 4), (64, 256), (130, 64)]:
        codes = _codes(spec, B)
        want = _ref_chain(tables, codes)
        got = lg_ops.lut_network_fused(tables, codes, block_b=block_b)
        assert got.shape == (B, 5)
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_routing_matrix_equals_gather_packing():
    """codes @ W == shift/add packing of gathered fan-in codes, also
    when conn repeats a feature within one sub-neuron."""
    rng = np.random.default_rng(0)
    n_in, n_out, A, F, bits = 16, 10, 2, 3, 2
    conn = rng.integers(0, n_in, (n_out, A, F)).astype(np.int32)
    conn[0, 0, :] = 7                      # degenerate: repeated feature
    codes = rng.integers(0, 2 ** bits, (30, n_in)).astype(np.int32)
    w = routing_matrix(jnp.asarray(conn), bits, n_in)
    got = (jnp.asarray(codes, jnp.float32) @ w).astype(jnp.int32)
    want = lg_ref.pack_index(jnp.asarray(codes)[:, conn], bits
                             ).reshape(30, n_out * A)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_make_network_fn_serving_entry():
    spec = LD.ModelSpec(name="t", in_features=16, widths=(24, 12, 5),
                        bits=2, fan_in=3, degree=1, adder_width=2)
    tables = _synth(spec)
    assert lg_ops.can_fuse(tables)
    fn = lg_ops.make_network_fn(tables)
    codes = _codes(spec, 48)
    want = _ref_chain(tables, codes)
    assert np.array_equal(np.asarray(fn(codes)), np.asarray(want))
    # repeated calls on the same shape reuse the compiled executable
    assert np.array_equal(np.asarray(fn(codes)), np.asarray(want))


def test_fused_vmem_accounting():
    """fused_vmem_bytes counts tables + routing + activation scratch,
    and a small net is well within budget."""
    spec = LD.ModelSpec(name="t", in_features=16, widths=(12, 5), bits=2,
                        fan_in=3, degree=1, adder_width=2)
    tables = _synth(spec)
    est = lg_ops.fused_vmem_bytes(tables, block_b=256)
    payload = LS.network_table_bytes(tables)
    assert est > sum(t.table_bytes for t in tables)  # routing + scratch
    assert payload > sum(t.table_bytes for t in tables)
    assert lg_ops.can_fuse(tables, block_b=256)


def test_pack_index_convention_stable():
    codes = jnp.asarray([[1, 2, 3]])
    assert int(lg_ref.pack_index(codes, 2)[0]) == 1 + (2 << 2) + (3 << 4)


def test_routing_matrices_cached_at_synthesis(monkeypatch):
    """Synthesis fills LayerTables.routing; tracing the fused network —
    even twice, with different static config — never rebuilds it."""
    spec = LD.ModelSpec(name="t", in_features=16, widths=(24, 12, 5),
                        bits=2, fan_in=3, degree=1, adder_width=2)
    tables = _synth(spec)
    assert all(t.routing is not None for t in tables)
    assert tables[0].routing.shape == (16, 24 * 2)

    calls = []
    real = lg_ops.routing_matrix

    def counting(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(lg_ops, "routing_matrix", counting)
    codes = _codes(spec, 24)
    want = _ref_chain(tables, codes)
    # two separate traces (block_b is a static arg -> distinct traces)
    a = lg_ops.lut_network_fused(tables, codes, block_b=16)
    b = lg_ops.lut_network_fused(tables, codes, block_b=8)
    assert calls == []
    assert np.array_equal(np.asarray(a), np.asarray(want))
    assert np.array_equal(np.asarray(b), np.asarray(want))


def test_fused_falls_back_without_routing_cache():
    """Hand-built tables (routing=None) still route exactly — the
    matrix is derived from conn at trace time as before."""
    import dataclasses
    spec = LD.ModelSpec(name="t", in_features=16, widths=(12, 5), bits=2,
                        fan_in=3, degree=1, adder_width=2)
    tables = [dataclasses.replace(t, routing=None) for t in _synth(spec)]
    codes = _codes(spec, 21)
    got = lg_ops.lut_network_fused(tables, codes)
    assert np.array_equal(np.asarray(got),
                          np.asarray(_ref_chain(tables, codes)))


def _nibble_bytes(entries):
    """int4 codes (R, K) -> (R, K // 2) bytes, low nibble first."""
    return (entries[:, 0::2] | entries[:, 1::2] << 4).astype(np.uint8)


@pytest.mark.parametrize("row_bytes", [64, 128, 256, 384, 1024, 4096])
@pytest.mark.parametrize("bits", [4, 8, 32])
def test_lookup_matches_numpy(bits, row_bytes):
    """``_lookup`` on the slab ``lookup_slab`` lays out reads exactly
    ``table[r, pos[r, j]]``: int4 nibbles, uint8 bytes and int32
    entries, byte and word layouts, partial-chunk tails, a row count
    off the 8-row tile, and the top field of each word with its high
    bit set (a sign bit once packed)."""
    R, L = 13, 128
    K = row_bytes * 8 // bits
    rng = np.random.default_rng(bits * 10007 + row_bytes)
    if bits == 32:
        table = rng.integers(-2 ** 31, 2 ** 31, (R, K), dtype=np.int64
                             ).astype(np.int32)
        table[:, -1] = -1
        stored = table
    else:
        table = rng.integers(0, 2 ** bits, (R, K)).astype(np.int32)
        per_word = 32 // bits
        table[:, per_word - 1::per_word] |= 1 << (bits - 1)
        stored = (_nibble_bytes(table) if bits == 4
                  else table.astype(np.uint8))
    assert stored.nbytes == R * row_bytes
    pos = rng.integers(0, K, (R, L)).astype(np.int32)
    pos[:, 0] = K - 1                       # top field of the last word
    pos[:, 1] = min(K, 32 // bits) - 1      # top field of the first word
    pos[:, 2] = 0

    slab, field = lookup_slab(jnp.asarray(stored), nibbles=bits == 4)
    assert field == bits
    words = word_packs(stored.shape[1], stored.itemsize)
    assert words == (bits != 32 and row_bytes > 128)
    assert slab.dtype == (jnp.int32 if words else stored.dtype)
    assert slab.nbytes == stored.nbytes

    def kernel(tab_ref, pos_ref, out_ref):
        out_ref[...] = _lookup(tab_ref, pos_ref[...], field)

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((R, L), jnp.int32),
        interpret=True)(slab, jnp.asarray(pos))
    want = np.take_along_axis(table, pos, axis=1)
    assert np.array_equal(np.asarray(got), want)


# execution plan summaries and fused VMEM claims of the paper rows, as
# the planner gave them before word packing (which changes neither)
PAPER_PLANS = {
    "jsc-xl-add2": (16, 2589184, dict(
        mode="fused", n_segments=1, n_in0=16, budget_bytes=12582912,
        pipeline=False, pack_int4=False, block_b_tuned=[1024],
        cut_widths=[], segments=[dict(layers=[0, 5], block_b=1024,
                                      vmem_bytes=2589184, n_in=16,
                                      n_out=5)])),
    "hdr-add2": (784, 6553280, dict(
        mode="fused", n_segments=1, n_in0=784, budget_bytes=12582912,
        pipeline=False, pack_int4=False, block_b_tuned=[1024],
        cut_widths=[], segments=[dict(layers=[0, 6], block_b=1024,
                                      vmem_bytes=6553280, n_in=784,
                                      n_out=10)])),
}


@pytest.mark.parametrize("name,spec_fn,byte_chunks,word_chunks", [
    ("jsc-xl-add2", PM.jsc_xl_add2, 13808, 3764),
    ("hdr-add2", PM.hdr_add2, 1998, 1998),
], ids=["jsc-xl-add2", "hdr-add2"])
def test_paper_width_exact_and_plan_stable(name, spec_fn, byte_chunks,
                                           word_chunks):
    """At the paper rows' widths and batch 256, the served engine
    matches ``lut_synth.lut_forward`` exactly; word packing cuts
    JSC-XL-Add2's lane-gather row-chunks 13,808 -> 3,764 and leaves
    HDR-Add2's int4 slabs (one chunk each) at 1,998; the execution plan
    and the VMEM claim are unchanged."""
    spec = spec_fn()
    plain = shaped_tables(spec, seed=3)
    # HDR-Add2's 2/3-bit codes are served int4-packed, as its artifact
    # loads; the reference reads the unpacked tables
    tables = LS.pack_tables_int4(plain) if name == "hdr-add2" else plain
    assert any(t.sub_packed for t in tables) == (name == "hdr-add2")
    n_in, vmem, summary = PAPER_PLANS[name]

    fn = lg_ops.make_network_fn(tables, n_in0=n_in)
    assert fn.execution_plan.summary() == summary
    assert lg_ops.fused_vmem_actual(tables, n_in0=n_in) == vmem
    assert lg_ops.fused_vmem_bytes(tables, n_in0=n_in) == vmem
    # one lane gather per 128 stored elements per row, before packing
    assert sum(int(np.prod(s.shape[:-1])) * -(-s.shape[-1] // LANES)
               for t in tables for s in (t.sub_table, t.add_table)
               if s.shape[-1]) == byte_chunks
    assert fn.lookup_row_chunks == word_chunks

    quant = spec.layer_specs()[0].in_quant
    x = jax.random.uniform(jax.random.key(5), (256, n_in),
                           minval=quant.low, maxval=quant.high)
    codes = quant.to_code(quant.clip(x)).astype(jnp.int32)
    got = np.asarray(fn(codes))
    want = LS.lut_forward(plain, x, quant)
    assert np.array_equal(np.asarray(LS.OUTPUT_QUANT.from_code(got)),
                          np.asarray(want))
