"""Round-trip contract of the LUT artifact store (repro/artifact).

The artifact is the deployment handoff unit, so the bar is BIT
exactness: for any synthesised network (packed uint8 or legacy int32
tables, int4-nibble or raw slab encoding), save -> load -> fused /
sharded forward must equal the in-memory synthesis output code for
code, across {1, 2, 4} virtual devices, over a seeded sweep of draws;
plus the negative paths a deployable format must refuse
loudly: content-hash mismatch, truncated slab file, future schema
version.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.artifact import (Artifact, ArtifactError, find_artifacts,
                            load_artifact, save_artifact)
from repro.artifact import store as A
from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.kernels.lut_gather import ops as lg_ops, ref as lg_ref

SPEC_KW = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
               degree=1, adder_width=2)


@functools.lru_cache(maxsize=None)
def _tables(pack: bool):
    spec = LD.ModelSpec(name="art-t", **SPEC_KW)
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


@functools.lru_cache(maxsize=None)
def _saved(tmp_root: str, pack: bool, int4: bool) -> str:
    spec, tables = _tables(pack)
    return save_artifact(os.path.join(tmp_root, f"p{pack}-i{int4}"),
                         tables, name="art-t", spec=spec, int4=int4)


@pytest.fixture(scope="module")
def art_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("artifacts"))


# ---------------------------------------------------------------------------
# round-trip bit-exactness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("int4", [True, False], ids=["int4", "raw"])
@pytest.mark.parametrize("pack", [True, False], ids=["uint8", "int32"])
def test_roundtrip_fused_bit_exact(art_root, pack, int4):
    spec, tables = _tables(pack)
    art = load_artifact(_saved(art_root, pack, int4))
    codes = _codes(spec, 53)
    want = _oracle(tables, codes)
    got = lg_ops.lut_network_fused(art.tables, codes)
    assert np.array_equal(np.asarray(got), want)
    # loaded metadata survives the trip too
    assert art.spec == spec
    for t_mem, t_disk in zip(tables, art.tables):
        assert t_disk.sub_table.dtype == t_mem.sub_table.dtype
        assert t_disk.out_quant == t_mem.out_quant


@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_roundtrip_sharded_bit_exact(art_root, lut_mesh, ndev):
    """Acceptance criterion: a loaded artifact through
    lut_network_fused_sharded on {1,2,4} virtual devices == in-memory
    synthesis, remainder batch included."""
    spec, tables = _tables(True)
    art = load_artifact(_saved(art_root, True, True))
    codes = _codes(spec, 37)
    want = _oracle(tables, codes)
    got = lg_ops.lut_network_fused_sharded(art.tables, codes,
                                           lut_mesh(ndev))
    assert np.array_equal(np.asarray(got), want)


def _check_one(art_root, B, ndev, pack, int4, seed):
    if jax.device_count() < ndev:
        pytest.skip(f"needs {ndev} devices")
    from repro.parallel.sharding import serving_mesh
    spec, tables = _tables(pack)
    art = load_artifact(_saved(art_root, pack, int4))
    codes = _codes(spec, B, seed=seed)
    want = _oracle(tables, codes)
    got = lg_ops.lut_network_fused_sharded(art.tables, codes,
                                           serving_mesh(ndev))
    assert np.array_equal(np.asarray(got), want), (B, ndev, pack, int4)


def _sweep_draws(n):
    """n seeded (B, ndev, pack, int4, seed) draws: remainder batches on
    every device count and both slab encodings."""
    rng = np.random.default_rng(4321)
    return [(int(rng.integers(1, 98)), int(rng.choice([1, 2, 4])),
             bool(rng.integers(2)), bool(rng.integers(2)),
             int(rng.integers(100)))
            for _ in range(n)]


SWEEP = _sweep_draws(12)


@pytest.mark.parametrize(
    "B,ndev,pack,int4,seed", SWEEP,
    ids=[f"B{B}-{nd}d-{'uint8' if p else 'int32'}-{'int4' if i else 'raw'}"
         f"-s{s}" for B, nd, p, i, s in SWEEP])
def test_seeded_sweep_artifact_roundtrip(art_root, B, ndev, pack, int4,
                                         seed):
    _check_one(art_root, B, ndev, pack, int4, seed)


def test_make_network_fn_accepts_artifact(art_root):
    """The kernels-layer serving entry unwraps a loaded bundle — the
    registry and launcher hand it artifacts directly."""
    spec, tables = _tables(True)
    art = load_artifact(_saved(art_root, True, True))
    fn = lg_ops.make_network_fn(art, block_b=64)
    codes = _codes(spec, 48)
    assert np.array_equal(np.asarray(fn(codes)), _oracle(tables, codes))


def _forced_plan(tables, spec, block_b=64):
    """A multi-segment plan for the module fixture net: budget shrunk
    to max(single-layer need, full/3) so the planner must cut."""
    widths = [t.conn.shape[0] for t in tables]
    need = max(lg_ops.fused_vmem_bytes(
        tables[i:i + 1], block_b,
        spec.in_features if i == 0 else widths[i - 1])
        for i in range(len(tables)))
    full = lg_ops.fused_vmem_bytes(tables, block_b, spec.in_features)
    budget = max(need, full // 3 + 1)
    return lg_ops.plan_segments(tables, block_b=block_b,
                                n_in0=spec.in_features, budget=budget,
                                prefer_int4=False), budget


def test_execution_plan_roundtrip_and_skips_tune(tmp_path, monkeypatch):
    """The plan-persistence contract: save -> load round-trips the
    partition plan (with per-segment block_b_tuned) verbatim, a
    plan-carrying artifact skips the tune_block_b sweep on load even
    under block_b="auto", planned-vs-replanned execution is bit-exact,
    and the plan does NOT perturb the content-addressed artifact id."""
    spec, tables = _tables(True)
    plan, budget = _forced_plan(tables, spec)
    assert plan.mode == "segmented" and plan.n_segments >= 2, plan
    p_plan = save_artifact(str(tmp_path / "with-plan"), tables,
                           name="art-t", spec=spec, plan=plan)
    p_bare = save_artifact(str(tmp_path / "no-plan"), tables,
                           name="art-t", spec=spec)
    # identical artifact id with or without a plan: the plan lives
    # outside the hashed content block
    assert os.path.basename(p_plan) == os.path.basename(p_bare)
    art = load_artifact(p_plan)
    assert art.execution_plan == plan.summary()
    assert lg_ops.SegmentPlan.from_summary(art.execution_plan) == plan
    assert load_artifact(p_bare).execution_plan is None

    probes = []
    monkeypatch.setattr(
        lg_ops, "tune_block_b",
        lambda *a, **k: probes.append(1) or (64, {64: 1.0}))
    fn = lg_ops.make_network_fn(art, block_b="auto")
    assert probes == [], "persisted plan must skip the block_b sweep"
    assert fn.execution_plan == plan

    codes = _codes(spec, 61)
    want = _oracle(tables, codes)
    replanned = lg_ops.make_network_fn(tables, block_b=64,
                                       n_in0=spec.in_features,
                                       budget=budget)
    assert np.array_equal(np.asarray(fn(codes)), want)
    assert np.array_equal(np.asarray(replanned(codes)), want)


def test_registry_serves_plan_carrying_artifact(tmp_path):
    """A segmented plan rides the artifact into the serving registry
    unchanged: the entry adopts it (observable in stats) and serves
    bit-exactly."""
    from repro.launch.registry import ModelRegistry

    spec, tables = _tables(True)
    plan, _ = _forced_plan(tables, spec)
    path = save_artifact(str(tmp_path / "seg"), tables, name="art-t",
                         spec=spec, plan=plan)
    codes = _codes(spec, 40)
    want = _oracle(tables, codes)
    with ModelRegistry(microbatch=64, deadline_s=5e-3) as reg:
        reg.register("seg", path)
        entry = reg.get("seg")
        assert entry.plan.mode == "segmented"
        assert entry.plan.n_segments == plan.n_segments
        st = reg.stats()["seg"]
        assert st["exec_mode"] == "segmented"
        assert st["exec_segments"] == plan.n_segments
        rows = np.asarray(codes)
        handles = [reg.submit("seg", r) for r in rows]
        got = np.stack([h.result(timeout=10.0) for h in handles])
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# format properties
# ---------------------------------------------------------------------------

def test_content_addressing_is_deterministic(tmp_path):
    spec, tables = _tables(True)
    p1 = save_artifact(str(tmp_path / "a"), tables, spec=spec)
    p2 = save_artifact(str(tmp_path / "b"), tables, spec=spec)
    a1, a2 = load_artifact(p1), load_artifact(p2)
    assert a1.artifact_id == a2.artifact_id
    assert os.path.basename(p1) == os.path.basename(p2)
    # ...and the id depends on table CONTENT
    spec2, tables2 = _tables(False)
    p3 = save_artifact(str(tmp_path / "c"), tables2, spec=spec2)
    assert load_artifact(p3).artifact_id != a1.artifact_id


def test_int4_packing_halves_eligible_slabs(tmp_path):
    """Two codes per byte for <=4-bit table codes, recorded in the
    manifest (with the ROADMAP in-kernel-unpack note) and transparent
    at load."""
    spec, tables = _tables(True)
    p_raw = save_artifact(str(tmp_path / "raw"), tables, spec=spec,
                          int4=False)
    p_i4 = save_artifact(str(tmp_path / "i4"), tables, spec=spec,
                         int4=True)
    man_raw = load_artifact(p_raw).manifest
    man_i4 = load_artifact(p_i4).manifest
    by_raw = {s["name"]: s for s in man_raw["slabs"]}
    packed = [s for s in man_i4["slabs"] if s["encoding"] == "int4"]
    assert packed, "default jsc tables must have int4-eligible slabs"
    for s in packed:
        assert s["nbytes"] * 2 >= by_raw[s["name"]]["nbytes"]
        assert s["nbytes"] <= by_raw[s["name"]]["nbytes"] // 2 + 1
    # the VMEM follow-up is recorded for the future in-kernel unpack
    assert "int4" in man_i4["notes"]
    assert "in-kernel" in man_i4["notes"]["int4"]
    assert man_raw["notes"] == {}
    # wide tables (16-bit output layer codes) must NOT nibble-pack
    out_slabs = [s for s in man_i4["slabs"]
                 if s["name"].endswith("add_table") and
                 s["name"].startswith(f"L{len(tables) - 1:02d}")]
    assert all(s["encoding"] == "raw" for s in out_slabs)


def test_manifest_carries_cost_model_and_provenance(tmp_path):
    from repro.core.cost_model import model_cost
    spec, tables = _tables(True)
    p = save_artifact(str(tmp_path), tables, spec=spec,
                      provenance={"train_steps": 0, "seed": 0})
    man = load_artifact(p).manifest
    assert man["cost_model"]["lut6"] == model_cost(spec).lut6
    assert man["provenance"]["train_steps"] == 0
    assert "created_unix" in man["provenance"]
    assert man["n_in"] == spec.in_features


def test_manifest_carries_search_provenance(tmp_path):
    """A searched-connectivity artifact ships its recipe: the
    ``search=`` dict (``lutdnn.search_provenance``) lands in the
    manifest and on ``Artifact.search`` — OUTSIDE the hashed content,
    so the same tables hash to the same artifact id with or without
    it (mirroring the ``plan=`` execution-plan precedent)."""
    spec, tables = _tables(True)
    cfgs = LD.search_sparsity_configs(spec, phase_boundary=3)
    init_state, _ = LD.make_search_step(spec, cfgs, lr=0.15)
    state = init_state(jax.random.key(0))
    prov = LD.search_provenance(spec, cfgs, state, n_steps=5, lr=0.15,
                                seeds=[3])
    p = save_artifact(str(tmp_path / "s"), tables, spec=spec, search=prov)
    art = load_artifact(p)
    assert art.search["algorithm"] == "sparselut-alg2"
    assert art.search["n_steps"] == 5
    assert art.search["seeds"] == [3]
    assert art.search["schedule"]["ramp_power"] == cfgs[0].ramp_power
    ledger = art.search["fan_in_ledger"]
    assert len(ledger) == len(spec.widths)
    for entry, ls in zip(ledger, spec.layer_specs()):
        assert isinstance(entry["target_fan_in"], int)
        assert entry["target_fan_in"] <= ls.total_fan_in
        assert entry["fan_in_min"] <= entry["fan_in_mean"] <= \
            entry["fan_in_max"]
    # survives the JSON round-trip on disk, not just in memory
    man = json.loads(open(os.path.join(p, A.MANIFEST)).read())
    assert man["search"] == art.search
    # outside the hashed content: identical id without it, and absent
    # search reads back as None
    p2 = save_artifact(str(tmp_path / "ns"), tables, spec=spec)
    assert load_artifact(p2).artifact_id == art.artifact_id
    assert load_artifact(p2).search is None


def test_find_artifacts_newest_first(tmp_path):
    spec, tables = _tables(True)
    _, tables2 = _tables(False)
    p1 = save_artifact(str(tmp_path), tables, name="m", spec=spec)
    os.utime(os.path.join(p1, A.MANIFEST), (1, 1))
    p2 = save_artifact(str(tmp_path), tables2, name="m", spec=spec)
    assert find_artifacts(str(tmp_path))[0] == p2
    assert load_artifact(str(tmp_path)).path == p2


# ---------------------------------------------------------------------------
# negative paths: a deployable format must refuse loudly
# ---------------------------------------------------------------------------

def _fresh(tmp_path) -> str:
    spec, tables = _tables(True)
    return save_artifact(str(tmp_path), tables, spec=spec)


def test_hash_mismatch_rejected(tmp_path):
    p = _fresh(tmp_path)
    slab = os.path.join(p, A.SLAB_FILE)
    blob = bytearray(open(slab, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(slab, "wb").write(bytes(blob))
    with pytest.raises(ArtifactError, match="hash mismatch"):
        load_artifact(p)
    # verify=False skips the (expensive at scale) re-hash by request
    assert isinstance(load_artifact(p, verify=False), Artifact)


def test_truncated_slab_rejected(tmp_path):
    p = _fresh(tmp_path)
    slab = os.path.join(p, A.SLAB_FILE)
    blob = open(slab, "rb").read()
    open(slab, "wb").write(blob[:len(blob) - 7])
    with pytest.raises(ArtifactError, match="truncated"):
        load_artifact(p)


def test_future_schema_version_rejected(tmp_path):
    p = _fresh(tmp_path)
    mpath = os.path.join(p, A.MANIFEST)
    man = json.load(open(mpath))
    man["schema_version"] = A.SCHEMA_VERSION + 1
    json.dump(man, open(mpath, "w"))
    with pytest.raises(ArtifactError, match="newer than this reader"):
        load_artifact(p)


def test_missing_and_foreign_dirs_rejected(tmp_path):
    with pytest.raises(ArtifactError, match="no artifact manifest"):
        load_artifact(str(tmp_path / "nope"))
    alien = tmp_path / "alien"
    alien.mkdir()
    (alien / A.MANIFEST).write_text(json.dumps({"format": "other"}))
    with pytest.raises(ArtifactError, match="not a lut-artifact"):
        load_artifact(str(alien))


# ---------------------------------------------------------------------------
# fleet transport primitives: copy (ship) + verify (admission gate)
# ---------------------------------------------------------------------------

def test_copy_artifact_preserves_identity_and_bytes(tmp_path):
    from repro.artifact import copy_artifact, verify_artifact

    src = _fresh(tmp_path / "src")
    dst = copy_artifact(src, str(tmp_path / "replica"))
    assert os.path.basename(dst) == os.path.basename(src)
    man = verify_artifact(dst)                   # hash-only, no arrays
    assert man["artifact_id"] == load_artifact(src).artifact_id
    # the copy loads and runs like the original
    spec, tables = _tables(True)
    codes = _codes(spec, 17)
    assert np.array_equal(
        np.asarray(lg_ops.lut_network_fused(
            load_artifact(dst).tables, codes, block_b=17)),
        _oracle(tables, codes))


def test_copy_artifact_refetch_replaces_corrupt_copy(tmp_path):
    from repro.artifact import copy_artifact, verify_artifact

    src = _fresh(tmp_path / "src")
    dst = copy_artifact(src, str(tmp_path / "replica"))
    slab = os.path.join(dst, A.SLAB_FILE)
    blob = bytearray(open(slab, "rb").read())
    blob[len(blob) // 2] ^= 0x01                 # transport bit flip
    open(slab, "wb").write(bytes(blob))
    with pytest.raises(ArtifactError, match="hash mismatch"):
        verify_artifact(dst)
    dst2 = copy_artifact(src, str(tmp_path / "replica"))   # re-fetch
    assert dst2 == dst
    verify_artifact(dst2)                        # clean again


def test_verify_artifact_rejects_truncation_and_missing(tmp_path):
    from repro.artifact import verify_artifact

    with pytest.raises(ArtifactError, match="no artifact manifest"):
        verify_artifact(str(tmp_path / "nope"))
    p = _fresh(tmp_path)
    slab = os.path.join(p, A.SLAB_FILE)
    blob = open(slab, "rb").read()
    open(slab, "wb").write(blob[:len(blob) - 3])
    with pytest.raises(ArtifactError, match="truncated"):
        verify_artifact(p)


def test_verify_artifact_rejects_structurally_corrupt_manifest(tmp_path):
    """A bit flip landing in manifest.json can keep it parseable while
    mangling keys — that must still be the typed ArtifactError (the
    fleet's delete-and-refetch path keys on it), never a raw
    KeyError."""
    from repro.artifact import verify_artifact

    p = _fresh(tmp_path)
    mpath = os.path.join(p, A.MANIFEST)
    man = json.load(open(mpath))
    man["slaps"] = man.pop("slabs")              # key mangled in flight
    json.dump(man, open(mpath, "w"))
    with pytest.raises(ArtifactError, match="structurally corrupt"):
        verify_artifact(p)
