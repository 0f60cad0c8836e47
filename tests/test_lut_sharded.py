"""Bit-exactness of the SHARDED fused LUT engine across device counts.

Contract: replicate-tables/shard-batch data parallelism is a pure
execution-layout change — for any synthesised network, any batch size
(including remainders that do not divide the device count), any device
count in {1, 2, 4}, and packed or legacy table dtypes, the shard_map
path agrees EXACTLY with the single-device jnp oracle.  The suite runs
under ``--xla_force_host_platform_device_count=4`` (tests/conftest.py)
so this is CI-checkable without accelerators.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.kernels.lut_gather import ops as lg_ops, ref as lg_ref

SPEC_KW = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
               degree=1, adder_width=2)


@functools.lru_cache(maxsize=None)
def _tables(pack: bool):
    spec = LD.ModelSpec(name="shard-t", **SPEC_KW)
    model = LD.init_model(jax.random.key(0), spec)
    return spec, LS.synthesise(model, spec, pack=pack)


def _oracle(tables, codes):
    for t in tables:
        codes = lg_ref.lut_layer(codes, t.conn, t.sub_table, t.add_table,
                                 t.in_bits, t.sub_bits)
    return np.asarray(codes)


def _codes(spec, B, seed=9):
    return jax.random.randint(
        jax.random.key(seed), (B, spec.in_features), 0,
        2 ** spec.layer_specs()[0].in_quant.bits).astype(jnp.int32)


@pytest.mark.parametrize("ndev", [1, 2, 4])
@pytest.mark.parametrize("pack", [True, False], ids=["uint8", "int32"])
def test_sharded_bit_exact_uneven_batch(lut_mesh, ndev, pack):
    """B=37 leaves a remainder on every multi-device mesh."""
    spec, tables = _tables(pack)
    codes = _codes(spec, 37)
    want = _oracle(tables, codes)
    got = lg_ops.lut_network_fused_sharded(tables, codes, lut_mesh(ndev))
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), want)


def _check_one(B, ndev, pack, seed):
    if jax.device_count() < ndev:
        pytest.skip(f"needs {ndev} devices")
    from repro.parallel.sharding import serving_mesh
    spec, tables = _tables(pack)
    codes = _codes(spec, B, seed=seed)
    want = _oracle(tables, codes)
    got = lg_ops.lut_network_fused_sharded(tables, codes,
                                           serving_mesh(ndev))
    assert np.array_equal(np.asarray(got), want), (B, ndev, pack, seed)


def _sweep_draws(n):
    """n seeded (B, ndev, pack, seed) draws: remainder batches on every
    device count, both table dtypes."""
    rng = np.random.default_rng(1234)
    return [(int(rng.integers(1, 98)), int(rng.choice([1, 2, 4])),
             bool(rng.integers(2)), int(rng.integers(100)))
            for _ in range(n)]


SWEEP = _sweep_draws(12)


@pytest.mark.parametrize(
    "B,ndev,pack,seed", SWEEP,
    ids=[f"B{B}-{nd}d-{'uint8' if p else 'int32'}-s{s}"
         for B, nd, p, s in SWEEP])
def test_seeded_sweep_sharded_matches_single_device_oracle(
        B, ndev, pack, seed):
    _check_one(B, ndev, pack, seed)


def test_sharded_per_layer_engine_also_exact(lut_mesh):
    """fused=False inside the shard_map (per-layer pallas_calls per
    shard) is the fallback for nets whose tables exceed VMEM."""
    spec, tables = _tables(True)
    codes = _codes(spec, 19)
    got = lg_ops.lut_network_fused_sharded(tables, codes, lut_mesh(4),
                                           fused=False)
    assert np.array_equal(np.asarray(got), _oracle(tables, codes))


def test_make_network_fn_sharded_serving_entry(lut_mesh):
    """mesh= builds a jitted sharded fn; repeated calls reuse it."""
    spec, tables = _tables(True)
    fn = lg_ops.make_network_fn(tables, mesh=lut_mesh(4))
    codes = _codes(spec, 48)
    want = _oracle(tables, codes)
    assert np.array_equal(np.asarray(fn(codes)), want)
    assert np.array_equal(np.asarray(fn(codes)), want)


@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_donate_sharded_bit_exact(lut_mesh, ndev):
    """Input donation on the SHARDED serving path is numerically
    invisible: fresh device buffers per call (the microbatcher's usage
    pattern), remainder batches included, all bit-exact vs the
    single-device oracle."""
    spec, tables = _tables(True)
    fn = lg_ops.make_network_fn(tables, mesh=lut_mesh(ndev), donate=True)
    for seed, B in ((0, 37), (1, 64), (2, 5)):
        codes = _codes(spec, B, seed=seed)
        want = _oracle(tables, np.asarray(codes))
        got = np.asarray(fn(codes))      # donates THIS buffer
        assert np.array_equal(got, want), (ndev, B)


def test_donate_is_wired_through_sharded_lowering(lut_mesh):
    """No-use-after-donate contract, pinned at the lowering: with
    donate=True the sharded fn marks its input a buffer donor (the
    runtime MAY reclaim it, so the serving loop must never reuse a
    submitted buffer — and doesn't: every microbatch is a fresh
    jnp.asarray); with donate=False the marker is absent.  Guards the
    old regression where donation was silently dropped off the mesh
    path."""
    spec, tables = _tables(True)
    mesh = lut_mesh(4)
    codes = _codes(spec, 64)
    donated = lg_ops.make_network_fn(tables, mesh=mesh, donate=True)
    plain = lg_ops.make_network_fn(tables, mesh=mesh, donate=False)
    txt_d = donated.lower(codes).as_text()
    txt_p = plain.lower(codes).as_text()
    marker = ("jax.buffer_donor", "tf.aliasing_output")
    assert any(m in txt_d for m in marker)
    assert not any(m in txt_p for m in marker)


def test_donated_input_never_yields_garbage(lut_mesh):
    """Passing the SAME buffer twice to a donating fn must either be
    refused by the runtime (buffer reclaimed -> error) or still return
    the bit-exact result — never silently corrupt output computed from
    reused memory."""
    spec, tables = _tables(True)
    fn = lg_ops.make_network_fn(tables, mesh=lut_mesh(4), donate=True)
    codes = _codes(spec, 48)
    want = _oracle(tables, np.asarray(codes))
    assert np.array_equal(np.asarray(fn(codes)), want)
    try:
        again = np.asarray(fn(codes))    # use-after-donate
    except RuntimeError:
        return                           # reclaimed: loud refusal is correct
    assert np.array_equal(again, want)


def test_sharded_output_is_batch_sharded(lut_mesh):
    """The output stays sharded over the mesh — downstream consumers
    (argmax, dequant) keep data parallelism without a reshard."""
    mesh = lut_mesh(4)
    spec, tables = _tables(True)
    codes = _codes(spec, 64)
    out = jax.jit(lambda c: lg_ops.lut_network_fused_sharded(
        tables, c, mesh))(codes)
    shard_devs = {s.device.id for s in out.addressable_shards}
    assert len(shard_devs) == 4
