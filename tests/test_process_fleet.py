"""Process-boundary fault harness for the socket-transport fleet
(launch/fleet.py transport="process" + launch/worker.py +
launch/transport.py).

The thread fleet (tests/test_fleet.py) pins the routing/2PC/verified-
distribution contracts inside one address space; this file re-pins the
SAME contracts across a real process boundary with real faults:

* **conformance** — a {1, 2, 4}-worker socket fleet answers bit-exact
  vs the single-host ``make_network_fn`` oracle; version tags and
  flush keys survive the wire;
* **SIGKILL mid-request** — a worker killed with requests in flight:
  zero dropped, zero hung, survivors absorb the re-dispatches;
* **partition during commit** — a socket severed between prepare and
  commit: the partitioned replica lands in ``not_cut``, the survivors
  cut over, and the worker PROCESS is still alive (a partition is not
  a death);
* **slab corruption in flight** — a bit flipped mid-stream is caught
  by the worker's per-slab SHA-256 re-hash (``verify_artifact`` on
  receipt), the transfer is re-fetched, and accounting shows exactly
  the corrupt attempt + the clean retry;
* **liveness** — a silently SIGKILLed worker (no router involvement)
  is detected by the heartbeat prober / connection-loss path and
  leaves the routing set with an epoch bump; membership epochs count
  every join and death.

Worker spawns cost seconds each, so fleets stay small; one 4-worker
soak runs every fault class under one Poisson stream.
"""
import functools
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
from repro.launch.fleet import LutFleet, ProcessReplica

SPEC_KW = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
               degree=1, adder_width=2)


@functools.lru_cache(maxsize=None)
def _net(seed: int):
    spec = LD.ModelSpec(name=f"pfleet-{seed}", **SPEC_KW)
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
    root = tmp_path_factory.mktemp("pfleet-artifacts")
    paths = {}
    for seed in (0, 1):
        spec, tables = _net(seed)
        paths[seed] = save_artifact(str(root), tables,
                                    name=f"pfleet-v{seed}", spec=spec)
    return paths


def _pfleet(n, **kw):
    kw.setdefault("microbatch", 8)
    kw.setdefault("deadline_s", 0.003)
    return LutFleet(n, transport="process", **kw)


# ---------------------------------------------------------------------------
# conformance: bit-exact over the wire
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_workers", [1, 2])
def test_process_fleet_bit_exact_vs_single_host_oracle(artifacts,
                                                       n_workers):
    rows = _rows(32)
    want = _want(0, rows)
    tag = load_artifact(artifacts[0]).artifact_id
    with _pfleet(n_workers) as fleet:
        assert all(isinstance(r, ProcessReplica) for r in fleet.replicas)
        # every worker is a live OS process, not a thread
        pids = {r.proc.pid for r in fleet.replicas}
        assert len(pids) == n_workers
        report = fleet.distribute_artifact(artifacts[0], "m")
        assert all(d.admitted and d.fetches == 1 for d in report.values())
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=60.0), want[i]), i
            assert h.version_tag == tag       # tags survive the wire
            assert h.flush_key is not None
        st = fleet.stats()
        assert sum(v["served"] for v in st.values()) == len(rows)
        if n_workers > 1:
            assert all(v["served"] > 0 for v in st.values()), st
        assert all(v["outstanding"] == 0 for v in st.values())


def test_four_worker_conformance_and_swap(artifacts):
    """The widest fast-lane fleet: 4 real workers serve bit-exact and
    cut over a two-phase swap consistently."""
    rows = _rows(40, seed=5)
    want = {0: _want(0, rows), 1: _want(1, rows)}
    tags = {s: load_artifact(artifacts[s]).artifact_id for s in (0, 1)}
    with _pfleet(4) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=60.0), want[0][i]), i
        rep = fleet.swap_fleet("m", artifacts[1])
        assert rep.new_tag == tags[1]
        assert not rep.not_cut
        assert set(fleet.admitted_tags("m").values()) == {tags[1]}
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=60.0), want[1][i]), i
            assert h.version_tag == tags[1]


# ---------------------------------------------------------------------------
# SIGKILL a worker with requests in flight
# ---------------------------------------------------------------------------

def test_sigkill_mid_request_zero_drops(artifacts):
    """SIGKILL a worker while its queue holds live requests AND while a
    producer keeps submitting: in-flight handles fail over through
    their FleetHandle, racing submits re-route, every request
    completes bit-exactly — zero dropped, zero hung."""
    rows = _rows(120, seed=7)
    want = _want(0, rows)
    with _pfleet(2, deadline_s=0.05) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        # long flush deadline: the victim still holds its queue when
        # the SIGKILL lands
        first = [fleet.submit("m", r) for r in rows[:40]]
        victim = max(fleet.stats().items(),
                     key=lambda kv: kv[1]["outstanding"])[0]
        victim_pid = fleet._replica(victim).proc.pid
        late: list = []

        def producer():
            for r in rows[40:]:
                late.append(fleet.submit("m", r))
                time.sleep(0.0005)

        t = threading.Thread(target=producer)
        t.start()
        fleet.kill_replica(victim)            # real SIGKILL
        t.join()
        assert fleet._replica(victim).proc.poll() is not None
        handles = first + late
        assert len(handles) == len(rows)      # zero dropped at submit
        retried = 0
        for i, h in enumerate(handles):
            out = h.result(timeout=60.0)      # zero hung
            assert np.array_equal(out, want[i]), i
            retried += h.retries
        assert retried > 0, "kill landed after all flushes — not in flight"
        st = fleet.stats()
        assert st[victim]["healthy"] is False
        assert all(v["outstanding"] == 0 for v in st.values())
        assert victim_pid not in (r.proc.pid for r in fleet.replicas
                                  if r.healthy)


# ---------------------------------------------------------------------------
# partition a socket during commit
# ---------------------------------------------------------------------------

def test_partition_during_commit_survivors_cut(artifacts):
    """Sever a worker's socket between prepare and commit: the
    partitioned replica lands in ``not_cut`` (its prepared engine is
    abandoned best-effort), the survivors cut over and serve the new
    version — and the partitioned worker PROCESS is still alive,
    because a partition is a network fault, not a host death."""
    rows = _rows(24, seed=11)
    want = _want(1, rows)
    tags = {s: load_artifact(artifacts[s]).artifact_id for s in (0, 1)}
    with _pfleet(2) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        prepared = fleet.prepare_swap("m", artifacts[1])
        epoch0 = fleet.membership()["epoch"]
        fleet.partition_replica("r1")
        # the connection-loss path marks it down with an epoch bump
        deadline = time.monotonic() + 10.0
        while (fleet.healthy_replicas() != ["r0"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert fleet.healthy_replicas() == ["r0"]
        assert fleet.membership()["epoch"] == epoch0 + 1
        rep = fleet.commit_swap(prepared)
        assert "r1" in rep.not_cut
        assert list(rep.blackout_s) == ["r0"]
        assert fleet.admitted_tags("m") == {"r0": tags[1]}
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=60.0), want[i]), i
            assert h.replica_id == "r0"
        # the worker survived the partition — only its link died
        assert fleet._replica("r1").proc.poll() is None


# ---------------------------------------------------------------------------
# slab corruption in flight
# ---------------------------------------------------------------------------

def test_corrupt_slab_in_flight_refetched(artifacts):
    """A bit flipped INSIDE the streaming transfer is rejected by the
    worker's on-receipt re-hash (``verify_artifact`` at admission),
    the transfer retries clean, and the rollout report counts exactly
    the corrupt attempt + the clean one."""
    rows = _rows(16, seed=13)
    want = _want(0, rows)
    tag = load_artifact(artifacts[0]).artifact_id
    with _pfleet(2) as fleet:
        fleet.inject_fetch_corruption("r1", n=1)
        report = fleet.distribute_artifact(artifacts[0], "m")
        assert report["r0"].admitted and report["r0"].fetches == 1
        assert report["r0"].verify_failures == 0
        assert report["r1"].admitted
        assert report["r1"].fetches == 2       # corrupt stream re-fetched
        assert report["r1"].verify_failures == 1
        # both workers computed the SAME content id from received bytes
        assert report["r0"].artifact_id == tag
        assert report["r1"].artifact_id == tag
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=60.0), want[i]), i


def test_exhausted_fetch_budget_excludes_worker(artifacts):
    """Persistent wire corruption: the worker is never admitted, the
    router excludes it, the clean worker carries all traffic."""
    rows = _rows(12, seed=17)
    want = _want(0, rows)
    with _pfleet(2, max_fetch_retries=1) as fleet:
        fleet.inject_fetch_corruption("r1", n=2)  # covers every attempt
        report = fleet.distribute_artifact(artifacts[0], "m")
        assert report["r0"].admitted
        assert not report["r1"].admitted
        assert report["r1"].verify_failures == 2
        assert fleet.admitted_tags("m").keys() == {"r0"}
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=60.0), want[i]), i
            assert h.replica_id == "r0"


# ---------------------------------------------------------------------------
# membership: heartbeat liveness + epochs
# ---------------------------------------------------------------------------

def test_heartbeat_detects_silent_worker_death(artifacts):
    """SIGKILL the worker process DIRECTLY (no router involvement, no
    injected flags): the liveness path — heartbeat probe misses or the
    connection-loss callback — must take the replica out of the
    routing set and bump the epoch, and traffic must keep flowing on
    the survivor."""
    rows = _rows(16, seed=19)
    want = _want(0, rows)
    with _pfleet(2, heartbeat_s=0.05, heartbeat_miss_limit=2) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        epoch0 = fleet.membership()["epoch"]
        fleet._replica("r1").proc.kill()       # silent host death
        deadline = time.monotonic() + 15.0
        while (fleet.healthy_replicas() != ["r0"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        detect_s = time.monotonic() - (deadline - 15.0)
        assert fleet.healthy_replicas() == ["r0"], "death never detected"
        m = fleet.membership()
        assert m["epoch"] == epoch0 + 1
        assert m["events"][-1]["event"] in ("heartbeat-dead", "conn-lost")
        assert m["replicas"] == {"r0": "up", "r1": "down"}
        assert detect_s < 10.0
        handles = [fleet.submit("m", r) for r in rows]
        for i, h in enumerate(handles):
            assert np.array_equal(h.result(timeout=60.0), want[i]), i
            assert h.replica_id == "r0"


def test_membership_epochs_count_joins_and_deaths(artifacts):
    with _pfleet(2) as fleet:
        m = fleet.membership()
        assert m["epoch"] == 2                 # one join per worker
        assert [e["event"] for e in m["events"]] == ["join", "join"]
        assert {e["replica_id"] for e in m["events"]} == {"r0", "r1"}
        fleet.kill_replica("r0")
        m = fleet.membership()
        assert m["epoch"] == 3
        assert m["events"][-1] == dict(m["events"][-1],
                                       event="killed", replica_id="r0")
        assert m["replicas"]["r0"] == "down"


# ---------------------------------------------------------------------------
# swap atomicity under load, over the wire
# ---------------------------------------------------------------------------

def test_no_mixed_version_microbatch_across_processes(artifacts):
    """Two-phase swap under live Poisson load over real sockets: every
    response's tag is exactly old or new, payloads match the engine
    the tag names, and no (replica, flush) microbatch mixes versions."""
    rows = _rows(240, seed=23)
    want = {0: _want(0, rows), 1: _want(1, rows)}
    tags = {s: load_artifact(artifacts[s]).artifact_id for s in (0, 1)}
    with _pfleet(2, microbatch=16, deadline_s=0.002) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        handles: list = []
        feeder = threading.Thread(target=lambda: handles.extend(
            replay_open_loop(fleet.client("m"), rows, rate=300.0,
                             timeout_s=240.0)))
        feeder.start()
        time.sleep(0.01)
        rep = fleet.commit_swap(fleet.prepare_swap("m", artifacts[1]))
        feeder.join()
        assert rep.new_tag == tags[1] and not rep.not_cut
        assert len(handles) == len(rows)
        flush_tags: dict = {}
        for i, h in enumerate(handles):
            out = h.result(timeout=60.0)       # zero dropped
            assert h.version_tag in (tags[0], tags[1]), h.version_tag
            src = 0 if h.version_tag == tags[0] else 1
            assert np.array_equal(out, want[src][i]), i
            flush_tags.setdefault(h.flush_key, set()).add(h.version_tag)
        assert all(len(s) == 1 for s in flush_tags.values())
        assert set(fleet.admitted_tags("m").values()) == {tags[1]}


# ---------------------------------------------------------------------------
# soak: every process-fault class under one stream
# ---------------------------------------------------------------------------

def test_process_fleet_soak_kill_partition_corrupt_swap(artifacts):
    """4 real workers under one continuous Poisson stream while: a
    slab corruption hits a transfer during the v0->v1 swap, a worker
    is SIGKILLed mid-stream, a second worker is partitioned, and a
    second swap (v1->v0) lands on the survivors — zero requests
    dropped or hung, every response matches the engine its tag names,
    membership saw every death."""
    rows = _rows(1500, seed=29)
    want = {0: _want(0, rows), 1: _want(1, rows)}
    tags = {s: load_artifact(artifacts[s]).artifact_id for s in (0, 1)}
    with _pfleet(4, microbatch=16, deadline_s=0.002,
                 heartbeat_s=0.1) as fleet:
        fleet.distribute_artifact(artifacts[0], "m")
        handles: list = []
        feeder = threading.Thread(target=lambda: handles.extend(
            replay_open_loop(fleet.client("m"), rows, rate=400.0,
                             timeout_s=600.0)))
        feeder.start()
        time.sleep(0.05)
        fleet.inject_fetch_corruption("r2", n=1)  # swap must re-fetch
        rep1 = fleet.swap_fleet("m", artifacts[1])
        fleet.kill_replica("r0")                  # SIGKILL mid-stream
        time.sleep(0.05)
        fleet.partition_replica("r3")             # sever a socket
        deadline = time.monotonic() + 15.0
        while (set(fleet.healthy_replicas()) != {"r1", "r2"}
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert set(fleet.healthy_replicas()) == {"r1", "r2"}
        rep2 = fleet.swap_fleet("m", artifacts[0])
        feeder.join()

        assert (rep1.new_tag, rep2.new_tag) == (tags[1], tags[0])
        assert fleet.stats()["r2"]["verify_failures"] == 1
        assert len(handles) == len(rows)
        for i, h in enumerate(handles):
            out = h.result(timeout=60.0)
            assert h.version_tag in (tags[0], tags[1]), h.version_tag
            src = 0 if h.version_tag == tags[0] else 1
            assert np.array_equal(out, want[src][i]), i
        live = fleet.admitted_tags("m")
        assert set(live) == {"r1", "r2"}
        assert set(live.values()) == {tags[0]}
        events = [e["event"] for e in fleet.membership()["events"]]
        assert events.count("join") == 4
        assert "killed" in events
        assert any(e in ("conn-lost", "heartbeat-dead") for e in events)
        # the partitioned worker's PROCESS survived
        assert fleet._replica("r3").proc.poll() is None
