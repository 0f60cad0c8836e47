"""Cross-engine conformance: ONE differential oracle for every LUT
inference engine (the harness is ``tests/lut_conformance.py``).

Seven execution paths exist for a synthesised LUT network —
per-layer Pallas (packed uint8 / legacy int32 / int4 nibble-packed),
fused single-kernel (same three layouts, grid-mode or double-buffered
pipeline), SEGMENTED (a cost-model plan chaining 2+ fused kernels with
inter-segment codes staged through HBM — forced here by shrinking the
planner's budget), shard_map data-parallel over {1, 2, 4} devices, and
the artifact round-trip (save -> content-addressed load, unpacked or
packed).  Every one of them is a pure execution-layout change, so they
must agree BIT-EXACTLY with the jnp reference chain
(kernels/lut_gather/ref.py) on the legacy int32 tables.

This file runs random network draws (layer widths, fan-in, code bits
spanning int4 / uint8 / int32 slabs, adder on/off, polynomial degree,
remainder batch sizes, ragged block_b) through the WHOLE engine
matrix: a seeded sweep in the default lane, and in the slow lane
(``pytest -m slow``) a longer one over all device counts with the
artifact round-trip.  The pinned corner cases are in
test_conformance_corners.py, the VMEM accounting in
test_conformance_vmem.py, segmented plans in
test_conformance_segments.py.
"""
import numpy as np
import pytest

from lut_conformance import (assert_conformant, build, oracle, random_codes,
                             random_draw)
from repro.core import lut_synth as LS
from repro.kernels.lut_gather import ops as lg_ops


def _sweep_draws(seed, n):
    rng = np.random.default_rng(seed)
    return [random_draw(rng) for _ in range(n)]


SWEEP = _sweep_draws(7, 6)


@pytest.mark.parametrize("kw,B,block_b", SWEEP,
                         ids=[f"draw{i}-B{B}-bb{bb}"
                              for i, (_, B, bb) in enumerate(SWEEP)])
def test_conformance_random_sweep(kw, B, block_b):
    """Seeded random draws through the full engine matrix."""
    assert_conformant(kw, B, block_b, ndevs=(2,))


LONG_SWEEP = _sweep_draws(11, 30)


@pytest.mark.slow
@pytest.mark.parametrize("kw,B,block_b", LONG_SWEEP,
                         ids=[f"draw{i}-B{B}-bb{bb}"
                              for i, (_, B, bb) in enumerate(LONG_SWEEP)])
def test_conformance_random_sweep_long(kw, B, block_b):
    """The long sweep: more draws, all device counts, artifact
    round-trip per draw."""
    assert_conformant(kw, B, block_b, ndevs=(1, 2, 4), artifact=True)


def test_tune_block_b_never_probes_over_budget(monkeypatch):
    """An over-budget network must not execute a fused timing probe (on
    real TPU that can OOM at serving start): tune_block_b raises, and
    make_network_fn(block_b="auto") silently routes to the per-layer
    engine instead of sweeping."""
    kw = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
              degree=1, adder_width=2)
    spec, packed, _ = build(tuple(sorted(kw.items())))
    monkeypatch.setattr(lg_ops, "FUSED_VMEM_BUDGET_BYTES", 1)
    with pytest.raises(ValueError, match="per-layer"):
        lg_ops.tune_block_b(packed, batch=64)
    probes = []
    monkeypatch.setattr(
        lg_ops, "tune_block_b",
        lambda *a, **k: probes.append(1) or (64, {64: 1.0}))
    fn = lg_ops.make_network_fn(packed, block_b="auto", tune_batch=64)
    assert probes == []                     # no sweep when not fusing
    codes = random_codes(spec, 48)
    assert np.array_equal(np.asarray(fn(codes)), oracle(packed, codes))


def test_save_artifact_int4_false_expands_packed_tables(tmp_path):
    """int4=False promises raw slabs everywhere, even when handed
    already-packed tables: the slab bytes (and artifact id) must match
    a raw save from unpacked tables, and the default load must see no
    packed flags."""
    from repro.artifact import load_artifact, save_artifact
    kw = dict(in_features=16, widths=(12, 7, 5), bits=2, fan_in=3,
              degree=2, adder_width=2)
    spec, packed, _ = build(tuple(sorted(kw.items())))
    int4 = LS.pack_tables_int4(packed)
    p_raw = save_artifact(str(tmp_path / "a"), packed, int4=False)
    p_from_packed = save_artifact(str(tmp_path / "b"), int4, int4=False)
    assert p_raw.split("-")[-1] == p_from_packed.split("-")[-1]
    art = load_artifact(p_from_packed)
    assert all(s["encoding"] == "raw" for s in art.manifest["slabs"])
    assert not any(t.sub_packed or t.add_packed for t in art.tables)
    codes = random_codes(spec, 23)
    assert np.array_equal(
        np.asarray(lg_ops.lut_network_fused(art.tables, codes)),
        oracle(packed, codes))


def test_tune_block_b_returns_valid_candidate():
    kw = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
              degree=1, adder_width=2)
    spec, packed, _ = build(tuple(sorted(kw.items())))
    best, timings = lg_ops.tune_block_b(packed, batch=64,
                                        candidates=(16, 32, 64, 256),
                                        iters=1)
    assert best in timings and timings
    assert all(bb <= 64 for bb in timings)          # clamped to batch
    assert all(t > 0 for t in timings.values())
    # "auto" wires the sweep into the serving entry
    fn = lg_ops.make_network_fn(packed, block_b="auto", tune_batch=64)
    codes = random_codes(spec, 48)
    assert np.array_equal(np.asarray(fn(codes)),
                          oracle(packed, codes))
