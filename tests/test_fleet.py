"""Fault-injection & consistency harness for the serving fleet
(launch/fleet.py).

The fleet lifts the one-host registry to N replicas; the contracts a
multi-host deployment must not lose are exactly what this file
injects faults against:

* **bit-exactness** — {1, 2, 4}-replica fleets answer every request
  bit-exactly vs the single-host ``make_network_fn`` oracle (a replica
  is an execution placement, never a numeric change);
* **zero drops under host death** — killing a replica with requests in
  flight fails those batches with the typed ``ReplicaCrashed``; their
  handles re-dispatch to healthy replicas and racing submits re-route
  (the registry's ``BatcherStopped`` absorption one level down), so
  every request completes and none hangs;
* **verified distribution** — a replica handed a bit-flipped slab
  refuses admission on the manifest-hash check, re-fetches, and the
  fleet's responses stay bit-exact vs the committed ``tests/golden/``
  vectors; a replica whose fetch budget is exhausted is excluded and
  the survivors carry the traffic;
* **swap atomicity** — under Poisson load spanning a two-phase fleet
  swap, every response's echoed version tag is EXACTLY the old or the
  new artifact id, every response's payload matches the engine its tag
  names, no microbatch ever mixes versions, and post-commit every
  replica reports the new id; a prepare failure on any replica aborts
  the cutover with all replicas still serving (and tagging) the old
  version.

The soak runs kill + corrupt + repeated swaps under one continuous
stream.
"""
import functools
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.artifact import load_artifact, save_artifact
from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.kernels.lut_gather import ops as lg_ops
from repro.launch.batching import replay_open_loop
from repro.launch.fleet import (FleetSwapError, LutFleet, NoHealthyReplica,
                                ReplicaCrashed)
from repro.launch.scheduler import (BATCH, DeadlineUnmeetable,
                                    interactive_tier)

GOLDEN_DIR = pathlib.Path(__file__).resolve().parent / "golden"

SPEC_KW = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
               degree=1, adder_width=2)


@functools.lru_cache(maxsize=None)
def _net(seed: int):
    spec = LD.ModelSpec(name=f"fleet-{seed}", **SPEC_KW)
    model = LD.init_model(jax.random.key(seed), spec)
    return spec, LS.synthesise(model, spec)


@functools.lru_cache(maxsize=None)
def _single_host_oracle(seed: int):
    """THE acceptance oracle: the one-host serving entry itself."""
    _, tables = _net(seed)
    return lg_ops.make_network_fn(tables, block_b=64)


def _want(seed: int, rows: np.ndarray) -> np.ndarray:
    return np.asarray(_single_host_oracle(seed)(jnp.asarray(rows)))


def _rows(n: int, seed: int = 3, width: int = 16) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 4, (n, width)).astype(np.int32)


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """v1/v2 artifacts of the same architecture (the swap payloads)."""
    root = tmp_path_factory.mktemp("fleet-artifacts")
    paths = {}
    for seed in (0, 1):
        spec, tables = _net(seed)
        paths[seed] = save_artifact(str(root), tables,
                                    name=f"fleet-v{seed}", spec=spec)
    return paths


# ---------------------------------------------------------------------------
# routing: bit-exactness + load spread + health exclusion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_replicas", [1, 2, 4])
def test_fleet_bit_exact_vs_single_host_oracle(artifacts, n_replicas):
    rows = _rows(48)
    want = _want(0, rows)
    with LutFleet(n_replicas, microbatch=8, deadline_s=0.003) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=30.0), want[i]), i
        st = fleet.stats()
        assert sum(v["served"] for v in st.values()) == len(rows)
        if n_replicas > 1:
            # least-outstanding routing spreads a burst over every host
            assert all(v["served"] > 0 for v in st.values()), st
        assert all(v["outstanding"] == 0 for v in st.values())


def test_router_excludes_dead_replica(artifacts):
    rows = _rows(24, seed=5)
    want = _want(0, rows)
    with LutFleet(3, microbatch=8, deadline_s=0.003) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        fleet.kill_replica("r1")
        assert fleet.healthy_replicas() == ["r0", "r2"]
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=30.0), want[i])
            assert h.replica_id in ("r0", "r2")
        assert fleet.stats()["r1"]["served"] == 0


def test_no_healthy_replica_raises_typed(artifacts):
    with LutFleet(1, microbatch=4, deadline_s=0.003) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        fleet.kill_replica("r0")
        with pytest.raises(NoHealthyReplica):
            fleet.submit("m", _rows(1)[0])
        # unknown model is the same typed refusal, not a hang
        with pytest.raises(NoHealthyReplica):
            fleet.submit("nope", _rows(1)[0])


# ---------------------------------------------------------------------------
# fault injection: replica crash with requests in flight
# ---------------------------------------------------------------------------

def test_replica_crash_mid_request_zero_drops(artifacts):
    """Kill a replica while its queue holds live requests AND while a
    producer keeps submitting: in-flight batches fail with the typed
    ReplicaCrashed and re-dispatch; racing submits re-route.  Every
    request completes bit-exactly, none hangs, none drops."""
    rows = _rows(160, seed=7)
    want = _want(0, rows)
    with LutFleet(3, microbatch=16, deadline_s=0.05) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        # long deadline: the victim's queue is guaranteed non-empty
        # when the kill lands (nothing has flushed yet)
        first = [fleet.submit("m", r) for r in rows[:60]]
        victim = max(fleet.stats().items(),
                     key=lambda kv: kv[1]["outstanding"])[0]
        stop = threading.Event()
        late: list = []

        def producer():
            for r in rows[60:]:
                late.append(fleet.submit("m", r))
                time.sleep(0.0005)
            stop.set()

        t = threading.Thread(target=producer)
        t.start()
        fleet.kill_replica(victim)
        t.join()
        handles = first + late
        assert len(handles) == len(rows)          # zero dropped at submit
        retried = 0
        for i, h in enumerate(handles):
            out = h.result(timeout=30.0)          # zero hung
            assert np.array_equal(out, want[i]), i
            retried += h.retries
        assert retried > 0, "kill landed after all flushes — not in flight"
        st = fleet.stats()
        assert all(v["outstanding"] == 0 for v in st.values())
        assert st[victim]["healthy"] is False


def test_persistent_engine_fault_times_out_instead_of_spinning(artifacts):
    """A replica whose engine fails every batch while still marked
    healthy (a fault class kill_replica doesn't model) must surface as
    a TimeoutError from result(), not an infinite re-dispatch spin:
    failed handles complete instantly, so the deadline is enforced
    between retry attempts."""
    with LutFleet(1, microbatch=4, deadline_s=0.003) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        # poison the engine gate but leave the replica routable
        fleet._replica("r0").crashed = True
        h = fleet.submit("m", _rows(1)[0])
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            h.result(timeout=0.5)
        assert time.monotonic() - t0 < 10.0
        assert h.retries > 0


def test_crashed_engine_raises_typed_error():
    """The injected death is the typed ReplicaCrashed at the engine
    gate — the batcher survives it and fails only the affected batch."""
    _, tables = _net(0)
    with LutFleet(2, microbatch=4, deadline_s=0.003) as fleet:
        r0 = fleet._replica("r0")
        with pytest.raises(ReplicaCrashed):
            r0.crashed = True
            fleet._engine_gate("r0")


# ---------------------------------------------------------------------------
# artifact distribution: corrupt copy -> excluded -> re-fetch; golden parity
# ---------------------------------------------------------------------------

def test_corrupt_fetch_verification_and_refetch_golden():
    """A replica handed a bit-flipped slab fails the manifest-hash
    admission check, deletes the copy, re-fetches clean — and the
    fleet's responses reproduce the committed golden vectors exactly
    (absolute parity, not just self-consistency)."""
    io = np.load(GOLDEN_DIR / "golden_io.npz")
    with LutFleet(2, microbatch=16, deadline_s=0.003) as fleet:
        fleet.inject_fetch_corruption("r1", n=1)
        report = fleet.distribute_artifact(str(GOLDEN_DIR), "golden")
        assert report["r0"].admitted and report["r0"].verify_failures == 0
        assert report["r1"].admitted and report["r1"].verify_failures == 1
        assert report["r1"].fetches == 2          # corrupt copy re-fetched
        assert report["r1"].artifact_id == str(io["artifact_id"])
        handles = [fleet.submit("golden", r) for r in io["inputs"]]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=30.0),
                                  io["outputs"][i]), i
            assert h.version_tag == str(io["artifact_id"])


def test_exhausted_fetch_budget_excludes_replica(artifacts):
    """Persistent corruption on one replica: it is never admitted, the
    router excludes it, and the healthy replica carries all traffic."""
    rows = _rows(20, seed=11)
    want = _want(0, rows)
    with LutFleet(2, microbatch=8, deadline_s=0.003,
                  max_fetch_retries=1) as fleet:
        fleet.inject_fetch_corruption("r1", n=2)   # covers every attempt
        report = fleet.distribute_artifact(artifacts[0], "m")
        assert report["r0"].admitted
        assert not report["r1"].admitted
        assert "verification" in report["r1"].error
        assert fleet.admitted_tags("m").keys() == {"r0"}
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=30.0), want[i])
            assert h.replica_id == "r0"


def test_concurrent_rollouts_report_their_own_fetch_counts(artifacts):
    """Two rollouts racing on the same fleet: each distribution report
    counts ITS OWN fetch attempts and verify failures, never the other
    rollout's.  (Regression: the report used to diff the replica's
    shared lifetime counters OUTSIDE the router lock, so a concurrent
    rollout's increments leaked into both reports.)"""
    with LutFleet(2, microbatch=8, deadline_s=0.003) as fleet:
        # two faults on r1: the racing rollouts share the fault budget
        # (either splits it 1+1 or one eats both), but each report must
        # count exactly the attempts ITS rollout made
        fleet.inject_fetch_corruption("r1", n=2)
        reports: dict = {}
        barrier = threading.Barrier(2)

        def rollout(model_id, src):
            barrier.wait()                    # maximal overlap
            reports[model_id] = fleet.distribute_artifact(src, model_id)

        threads = [threading.Thread(target=rollout, args=("a", artifacts[0])),
                   threading.Thread(target=rollout, args=("b", artifacts[1]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for mid in ("a", "b"):
            rep = reports[mid]
            # r0 has no faults: ONE fetch per rollout — a report showing
            # more has counted the concurrent rollout's attempt
            assert rep["r0"].admitted
            assert rep["r0"].fetches == 1, (mid, rep["r0"])
            assert rep["r0"].verify_failures == 0
            # r1: every failure this rollout saw triggered exactly one
            # retry, and the final attempt admitted
            assert rep["r1"].admitted
            assert rep["r1"].fetches == rep["r1"].verify_failures + 1, \
                (mid, rep["r1"])
        # the per-rollout tallies partition the lifetime totals exactly
        st = fleet.stats()
        assert st["r0"]["fetches"] == 2
        assert st["r1"]["fetches"] == 4
        assert st["r1"]["verify_failures"] == 2
        assert (reports["a"]["r1"].fetches
                + reports["b"]["r1"].fetches) == 4
        assert (reports["a"]["r1"].verify_failures
                + reports["b"]["r1"].verify_failures) == 2


# ---------------------------------------------------------------------------
# two-phase coordinated swap
# ---------------------------------------------------------------------------

def test_two_phase_swap_atomicity_under_poisson_load(artifacts):
    """The acceptance criterion: a fleet swap under live Poisson load
    serves every request with a version tag that is EXACTLY the old or
    the new artifact id, the payload matches the engine the tag names,
    no microbatch mixes versions, and post-commit every replica
    reports the new id."""
    # ~2.7s stream: the fleet-wide prepare (parallel fetch + verify +
    # engine warm per replica, ~1s on this box) must COMMIT while
    # requests are still arriving, otherwise the swap trivially lands
    # after the load
    rows = _rows(800, seed=13)
    want = {0: _want(0, rows), 1: _want(1, rows)}
    with LutFleet(3, microbatch=16, deadline_s=0.002) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        tags = {s: load_artifact(artifacts[s]).artifact_id for s in (0, 1)}
        handles: list = []
        feeder = threading.Thread(target=lambda: handles.extend(
            replay_open_loop(fleet.client("m"), rows, rate=300.0)))
        feeder.start()
        time.sleep(0.01)                          # land the swap mid-stream
        prepared = fleet.prepare_swap("m", artifacts[1])
        # phase 1 leaves every replica still serving + tagging v0
        assert set(fleet.admitted_tags("m").values()) == {tags[0]}
        rep = fleet.commit_swap(prepared)
        feeder.join()

        assert rep.new_tag == tags[1]
        assert set(rep.old_tags.values()) == {tags[0]}
        assert set(fleet.admitted_tags("m").values()) == {tags[1]}
        assert len(handles) == len(rows)
        by_tag = {tags[0]: 0, tags[1]: 0}
        flush_tags: dict = {}
        for i, h in enumerate(handles):
            out = h.result(timeout=30.0)          # zero dropped
            assert h.version_tag in by_tag, h.version_tag
            by_tag[h.version_tag] += 1
            # the payload matches the engine the tag CLAIMS served it
            src = 0 if h.version_tag == tags[0] else 1
            assert np.array_equal(out, want[src][i]), i
            # and no microbatch ever mixes versions
            flush_tags.setdefault(h.flush_key, set()).add(h.version_tag)
        assert all(len(s) == 1 for s in flush_tags.values())
        assert by_tag[tags[1]] > 0                # the swap took effect
        # post-commit, fresh traffic is uniformly on the new version
        fresh = [fleet.submit("m", r) for r in rows[:16]]
        for i, h in enumerate(fresh):
            assert np.array_equal(h.result(timeout=30.0), want[1][i])
            assert h.version_tag == tags[1]


def test_swap_prepare_failure_aborts_fleet_wide(artifacts):
    """Two-phase semantics: a replica that cannot verify the new
    artifact aborts the WHOLE cutover before any commit — every
    replica keeps serving (and tagging) the old version."""
    rows = _rows(12, seed=17)
    want = _want(0, rows)
    with LutFleet(2, microbatch=8, deadline_s=0.003,
                  max_fetch_retries=0) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        old_tag = load_artifact(artifacts[0]).artifact_id
        fleet.inject_fetch_corruption("r1", n=1)
        with pytest.raises(FleetSwapError, match="still serve the old"):
            fleet.prepare_swap("m", artifacts[1])
        assert set(fleet.admitted_tags("m").values()) == {old_tag}
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=30.0), want[i])
            assert h.version_tag == old_tag


def test_commit_skips_replica_killed_after_prepare(artifacts):
    """A host death between prepare and commit must not wedge the
    cutover: the dead replica's prepared engine stands down, the
    survivors cut over and serve."""
    rows = _rows(16, seed=19)
    want = _want(1, rows)
    with LutFleet(2, microbatch=8, deadline_s=0.003) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        prepared = fleet.prepare_swap("m", artifacts[1])
        fleet.kill_replica("r1")
        rep = fleet.commit_swap(prepared)
        assert list(rep.blackout_s) == ["r0"]
        assert rep.not_cut == {"r1": "replica unhealthy at commit"}
        assert fleet.admitted_tags("m") == {"r0": rep.new_tag}
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=30.0), want[i])


def test_commit_absorbs_replica_killed_mid_commit(artifacts):
    """The narrower race: a replica passes the ``healthy`` check but
    its registry dies before ``registry.commit`` runs (a kill landing
    INSIDE the commit loop).  The commit exception must not escape
    mid-loop — that would leave the fleet half-old/half-new with no
    report and the remaining prepared entries never abandoned.  The
    racing replica is recorded in ``not_cut``, the survivors cut over
    and serve.  (Closing the registry while ``healthy`` stays True IS
    the racing state: the health check passes, the commit fails.)"""
    rows = _rows(24, seed=29)
    want = _want(1, rows)
    with LutFleet(3, microbatch=8, deadline_s=0.003) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        prepared = fleet.prepare_swap("m", artifacts[1])
        fleet._replica("r1").registry.close()
        assert fleet._replica("r1").healthy          # the race, exactly
        rep = fleet.commit_swap(prepared)            # must NOT raise
        assert set(rep.not_cut) == {"r1"}
        assert "r1" not in rep.old_tags
        assert sorted(rep.blackout_s) == ["r0", "r2"]
        assert sorted(rep.drained_requests) == ["r0", "r2"]
        # survivors serve the new version; submits racing onto the dead
        # registry re-route (UnknownModelError absorption in _dispatch)
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=30.0), want[i])
            assert h.version_tag == rep.new_tag
            assert h.replica_id in ("r0", "r2")


# ---------------------------------------------------------------------------
# SLO tiers through the fleet (launch/scheduler.py wiring)
# ---------------------------------------------------------------------------

def test_fleet_tier_routing_bit_exact(artifacts):
    """A tiered fleet serves mixed interactive/batch traffic bit-exact
    vs the single-host oracle — tier-aware routing changes placement,
    never numerics — and generous deadlines shed nothing."""
    rows = _rows(48, seed=31)
    want = _want(0, rows)
    tiers = [interactive_tier(60.0), BATCH]
    with LutFleet(2, microbatch=8, deadline_s=0.003,
                  slo_tiers=tiers, work_stealing=True) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        handles = [fleet.submit("m", r, tier=tiers[i % 2])
                   for i, r in enumerate(rows)]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=30.0), want[i]), i
        assert fleet.sheds == 0
        st = fleet.stats()
        assert sum(v["served"] for v in st.values()) == len(rows)


def test_fleet_sheds_provably_late_request_typed(artifacts):
    """Once every replica has flush history, a deadline-class request
    whose deadline is provably unmeetable on ALL of them is shed with
    the typed DeadlineUnmeetable BEFORE dispatch (fleet.sheds counts
    it) — while batch-tier traffic keeps flowing."""
    rows = _rows(32, seed=37)
    with LutFleet(2, microbatch=4, deadline_s=0.003,
                  slo_tiers=[interactive_tier(60.0), BATCH]) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        # warm BOTH replicas into kernel/service history
        warm = [fleet.submit("m", r, tier=BATCH) for r in rows]
        for h in warm:
            h.result(timeout=30.0)
        assert all(
            r.registry.estimate_delay_s("m") is not None
            for r in fleet.replicas)
        with pytest.raises(DeadlineUnmeetable, match="shed"):
            fleet.submit("m", rows[0], tier=interactive_tier(1e-9))
        assert fleet.sheds == 1
        ok = fleet.submit("m", rows[0], tier=BATCH)  # still serving
        assert np.array_equal(ok.result(timeout=30.0),
                              _want(0, rows[:1])[0])


# ---------------------------------------------------------------------------
# soak: every fault class under one continuous stream
# ---------------------------------------------------------------------------

def test_fleet_soak_kill_corrupt_swap_zero_drops(artifacts):
    """Long Poisson stream over a 4-replica fleet while: a fetch
    corruption hits a replica during the v0->v1 swap's prepare, a
    replica dies mid-stream, and a second swap (v1->v0) lands — zero
    requests dropped or hung, every response matches the engine its
    tag names, fleet consistent at the end."""
    rows = _rows(3000, seed=23)
    want = {0: _want(0, rows), 1: _want(1, rows)}
    tags = {s: load_artifact(artifacts[s]).artifact_id for s in (0, 1)}
    with LutFleet(4, microbatch=16, deadline_s=0.002) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        handles: list = []
        feeder = threading.Thread(target=lambda: handles.extend(
            replay_open_loop(fleet.client("m"), rows, rate=500.0,
                             timeout_s=240.0)))
        feeder.start()
        time.sleep(0.05)
        fleet.inject_fetch_corruption("r2", n=1)   # swap 1 must re-fetch
        rep1 = fleet.swap_fleet("m", artifacts[1])
        fleet.kill_replica("r0")
        time.sleep(0.05)
        rep2 = fleet.swap_fleet("m", artifacts[0])
        feeder.join()

        assert (rep1.new_tag, rep2.new_tag) == (tags[1], tags[0])
        assert fleet.stats()["r2"]["verify_failures"] == 1
        assert len(handles) == len(rows)
        served_by_tag = {tags[0]: 0, tags[1]: 0}
        for i, h in enumerate(handles):
            out = h.result(timeout=30.0)
            assert h.version_tag in served_by_tag, h.version_tag
            served_by_tag[h.version_tag] += 1
            src = 0 if h.version_tag == tags[0] else 1
            assert np.array_equal(out, want[src][i]), i
        assert served_by_tag[tags[1]] > 0
        live = fleet.admitted_tags("m")
        assert "r0" not in live
        assert set(live.values()) == {tags[0]}
        st = fleet.stats()
        assert all(v["outstanding"] == 0 for v in st.values())
