"""Cross-engine conformance, deterministic corner sweep: every LUT
inference engine (per-layer and fused Pallas with int32 / uint8 / int4
tables, the double-buffered pipeline, segmented plans, shard_map over
{1, 2, 4} devices, and for ``deep4`` the artifact round-trip) against
the jnp oracle of ``tests/lut_conformance.py``, on corner cases pinned
so that coverage does not rest on random draws."""
import numpy as np
import pytest

from lut_conformance import assert_conformant, build, oracle, random_codes
from repro.core import lut_synth as LS
from repro.kernels.lut_gather import ops as lg_ops

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
    assert_conformant(kw, B, block_b, ndevs=(1, 2, 4),
                      artifact=(name == "deep4"))


def test_adder_off_through_packed_kernel():
    """Regression for the zero-width add-table binding: an adder-off
    layer's dummy must never be read or treated as packed — the
    per-layer kernel accepts add_packed=True with an EMPTY add table
    and stays exact (the flag is forced off with use_adder)."""
    kw = dict(in_features=16, widths=(12, 5), bits=2, fan_in=3,
              degree=1, adder_width=1)
    spec, packed, legacy = build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    assert all(t.add_table.shape[-1] == 0 for t in int4)
    assert any(t.sub_packed for t in int4)
    codes = random_codes(spec, 19)
    want = oracle(legacy, codes)
    out = codes
    for t in int4:
        out = lg_ops.lut_layer(out, t.conn, t.sub_table, t.add_table,
                               t.in_bits, t.sub_bits,
                               sub_packed=t.sub_packed,
                               add_packed=True)   # hostile flag: no-op
    assert np.array_equal(np.asarray(out), want)
