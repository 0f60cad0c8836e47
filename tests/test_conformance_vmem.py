"""VMEM accounting: the fusion-eligibility estimate
(``ops.fused_vmem_bytes``) is pinned against the ACTUAL flattened slab
+ scratch allocation (``ops.fused_vmem_actual``) for packed and
unpacked layouts, pipelined and grid tiles, so the estimator cannot
silently drift from what the kernel binds."""
import pytest

from lut_conformance import build
from repro.core import lut_synth as LS
from repro.kernels.lut_gather import ops as lg_ops

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
    spec, packed, legacy = build(tuple(sorted(kw.items())))
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
    spec, packed, _ = build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    u8 = sum(t.table_bytes for t in packed)
    i4 = sum(t.table_bytes for t in int4)
    assert i4 <= 0.55 * u8, (i4, u8)
    assert lg_ops.fused_vmem_bytes(int4, 256, spec.in_features) < \
        lg_ops.fused_vmem_bytes(packed, 256, spec.in_features)
