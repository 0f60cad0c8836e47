"""SLO-tiered scoreboard scheduler (launch/scheduler.py).

Layered contracts:
  * **scoreboard** — the pending-matrix slot array issues deadline-class
    requests earliest-deadline-first with best-effort backfill, ages
    within a class, and never refuses an insert (grow-on-full);
  * **admission control** — a deadline-class request whose queue-depth
    x kernel-time estimate provably misses its deadline is shed AT
    SUBMIT with the typed ``DeadlineUnmeetable`` (and never before any
    flush history exists — no estimate, no shed);
  * **work-stealing** — an idle batcher executes a backlogged sibling's
    overflow flushes bit-exactly, through the StealGroup of a registry;
  * **SLO attainment** — under mixed 2-tier Poisson load at
    1.5x the sustainable rate: interactive deadline attainment >= 95%
    over admitted requests, every shed typed, zero silent drops, zero
    hung handles, batch-tier throughput >= 0.7x the FIFO baseline.
"""
import functools
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.kernels.lut_gather import ref as lg_ref
from repro.launch.batching import MicroBatcher, RequestHandle
from repro.launch.registry import ModelRegistry
from repro.launch.scheduler import (BATCH, DeadlineUnmeetable, Scoreboard,
                                    ScoreboardScheduler, StealGroup,
                                    interactive_tier, kernel_estimate_s,
                                    replay_tiered_open_loop, tier_report)

N_FEAT = 4


def _engine(batch):
    return batch.astype(np.int64) * 10 + batch.sum(axis=1, keepdims=True)


def _handle(deadline_at=None, t_submit=None):
    return RequestHandle(x=np.zeros(N_FEAT, np.int32),
                         t_submit=time.monotonic() if t_submit is None
                         else t_submit,
                         deadline_at=deadline_at)


# ---------------------------------------------------------------------------
# scoreboard issue order
# ---------------------------------------------------------------------------

def test_scoreboard_edf_with_besteffort_backfill():
    """Urgent slots issue earliest-deadline-first; best-effort slots
    backfill strictly after every urgent one, oldest first — the issue
    scan, not arrival order, decides."""
    sb = Scoreboard()
    be1 = _handle()                       # best-effort, oldest
    u_late = _handle(deadline_at=100.0)
    u_early = _handle(deadline_at=5.0)
    be2 = _handle()
    for h in (be1, u_late, u_early, be2):
        sb.insert(h)
    assert sb.depth() == 4
    assert sb.issue(3) == [u_early, u_late, be1]
    assert sb.issue(8) == [be2]
    assert sb.depth() == 0 and sb.issue(1) == []


def test_scoreboard_ages_within_class():
    """Equal deadlines (and all best-effort requests) issue in age
    order — the seq counter is the tie-break, so no slot starves."""
    sb = Scoreboard()
    urgents = [_handle(deadline_at=7.0) for _ in range(5)]
    efforts = [_handle() for _ in range(5)]
    for u, b in zip(urgents, efforts):
        sb.insert(b)
        sb.insert(u)
    assert sb.issue(10) == urgents + efforts


def test_scoreboard_grows_and_partial_issue_keeps_slots():
    """The slot array doubles when full (insert never refuses) and a
    partial issue leaves the overflow in place for the next round."""
    sb = Scoreboard(n_slots=2)
    hs = [_handle(deadline_at=float(i)) for i in range(11)]
    for h in hs:
        sb.insert(h)
    assert sb.depth() == 11
    assert sb.issue(4) == hs[:4]
    assert sb.depth() == 7
    assert sb.issue(100) == hs[4:]
    # freed slots are reused
    sb.insert(hs[0])
    assert sb.depth() == 1


def test_urgent_ahead_excludes_besteffort_and_later_deadlines():
    sb = Scoreboard()
    sb.insert(_handle())                  # best-effort: never ahead
    sb.insert(_handle(deadline_at=1.0))
    sb.insert(_handle(deadline_at=2.0))
    sb.insert(_handle(deadline_at=9.0))   # later: issues after us
    assert sb.urgent_ahead(2.0) == 2
    assert sb.urgent_ahead(0.5) == 0
    assert sb.urgent_ahead(100.0) == 3


def test_oldest_t_submit_tracks_first_pending():
    sb = Scoreboard()
    assert sb.oldest_t_submit() is None
    a = _handle(t_submit=5.0)
    b = _handle(t_submit=3.0, deadline_at=1.0)  # younger INSERT wins age
    sb.insert(a)
    sb.insert(b)
    assert sb.oldest_t_submit() == 5.0    # insertion order, not deadline
    sb.issue(1)                           # EDF pops b first
    assert sb.oldest_t_submit() == 5.0
    sb.issue(1)
    assert sb.oldest_t_submit() is None


# ---------------------------------------------------------------------------
# kernel estimation + admission control
# ---------------------------------------------------------------------------

def test_kernel_estimate_ignores_failed_flushes():
    class F:
        def __init__(self, k, failed):
            self.kernel_s, self.failed = k, failed
    assert kernel_estimate_s([]) is None
    assert kernel_estimate_s([F(0.001, True)]) is None
    flushes = [F(0.004, False), F(0.5, True), F(0.006, False)]
    assert kernel_estimate_s(flushes) == pytest.approx(0.005)


def test_no_shed_before_flush_history():
    """Without kernel-time history there is no estimate, hence no
    provable miss — the very first requests always admit (and get
    served), even with an absurdly tight deadline."""
    sched = ScoreboardScheduler()
    with MicroBatcher(_engine, microbatch=4, deadline_s=0.002,
                      n_features=N_FEAT, scheduler=sched) as mb:
        h = mb.submit(np.arange(N_FEAT), tier=interactive_tier(1e-9))
        out = h.result(timeout=5.0)
    assert np.array_equal(out, _engine(np.arange(N_FEAT)[None])[0])
    assert sched.sheds == 0


def test_admission_sheds_with_typed_rejection():
    """Once the backlog provably exceeds the deadline, submit raises
    the TYPED DeadlineUnmeetable (a RuntimeError subclass), counts the
    shed, and the request never enters the scoreboard — while
    best-effort and wide-deadline requests keep admitting."""
    gate = threading.Event()
    entered = threading.Event()

    def slow(batch):
        entered.set()
        gate.wait(5.0)
        time.sleep(0.02)
        return _engine(batch)

    sched = ScoreboardScheduler()
    with MicroBatcher(slow, microbatch=2, deadline_s=0.001,
                      n_features=N_FEAT, scheduler=sched) as mb:
        gate.set()
        warm = mb.submit(np.arange(N_FEAT), tier=BATCH)
        warm.result(timeout=5.0)          # one flush -> kernel history
        gate.clear()                      # hold the engine: backlog grows
        entered.clear()
        backlog = [mb.submit(np.arange(N_FEAT),
                             tier=interactive_tier(60.0))
                   for _ in range(10)]
        # the batcher is now held at the gate with the first backlog
        # flush — the remaining depth is stable, so the no-queue check
        # below races nothing
        assert entered.wait(5.0)
        depth_before = sched.scoreboard.depth()
        with pytest.raises(DeadlineUnmeetable, match="shed"):
            mb.submit(np.arange(N_FEAT), tier=interactive_tier(0.005))
        assert isinstance(DeadlineUnmeetable("x"), RuntimeError)
        assert sched.sheds == 1
        assert sched.scoreboard.depth() == depth_before  # never queued
        ok = mb.submit(np.arange(N_FEAT), tier=BATCH)    # still admits
        gate.set()
        for h in backlog + [ok]:
            assert h.result(timeout=10.0) is not None
    assert sched.sheds == 1


def test_estimate_counts_inflight_flush():
    """The delay estimate includes a flush already executing — without
    it, steady-state overload admits boundary requests that miss by a
    full kernel time."""
    started, gate = threading.Event(), threading.Event()

    def slow(batch):
        started.set()
        gate.wait(5.0)
        return _engine(batch)

    sched = ScoreboardScheduler()
    with MicroBatcher(slow, microbatch=2, deadline_s=0.001,
                      n_features=N_FEAT, scheduler=sched) as mb:
        gate.set()
        mb.submit(np.arange(N_FEAT), tier=BATCH).result(timeout=5.0)
        # the flush fed both estimators; admission uses the whole-flush
        # service quantile, which can only exceed the kernel median
        per_flush = sched.service_estimate_s()
        assert per_flush >= sched.kernel_estimate_s()
        idle_est = sched.estimate_delay_s()
        gate.clear()
        started.clear()
        h = mb.submit(np.arange(N_FEAT), tier=BATCH)
        assert started.wait(5.0)          # flush now in flight
        busy_est = sched.estimate_delay_s()
        assert busy_est == pytest.approx(idle_est + per_flush)
        gate.set()
        h.result(timeout=5.0)


def test_flush_wakes_for_admitted_hard_deadline():
    """An admitted deadline-class request must not wait out a batcher
    flush deadline LONGER than its own hard deadline: the collect wait
    wakes at min(oldest + deadline_s, earliest deadline_at - service
    estimate).  (Regression: the phase-2 wait used only the flush
    timer, so with deadline_s=2 s a lone interactive request with a
    250 ms SLO sat in the scoreboard for the full 2 s — admission
    control admitted it, then the batcher's own timer missed it.)"""
    sched = ScoreboardScheduler()
    with MicroBatcher(_engine, microbatch=8, deadline_s=2.0,
                      n_features=N_FEAT, scheduler=sched) as mb:
        # one full flush first: the estimator has history, so the wake
        # lands a service interval BEFORE the hard deadline
        warm = [mb.submit(np.arange(N_FEAT), tier=BATCH) for _ in range(8)]
        for h in warm:
            h.result(timeout=5.0)
        slo = 0.25
        h = mb.submit(np.arange(N_FEAT), tier=interactive_tier(slo))
        out = h.result(timeout=5.0)
    assert np.array_equal(out, _engine(np.arange(N_FEAT)[None])[0])
    # served within its own SLO (+ scheduling jitter), NOT the 2 s
    # flush deadline — pre-fix this latency is ~2 s and the SLO is lost
    assert h.latency_s <= slo + 0.35, h.latency_s
    assert h.t_done <= h.deadline_at + 0.35
    # and it genuinely waited for backfill rather than flushing a
    # 1/8 batch immediately (the flush timer still batches)
    assert h.latency_s >= 0.05 * slo
    tail = mb.flushes[-1]
    assert tail.fill == 1 and tail.deadline_hit


def test_deadline_wake_still_batches_follow_up_traffic():
    """The SLO-aware wake must not degenerate into flush-per-request:
    requests arriving within the wait window still coalesce into one
    flush ahead of the earliest deadline."""
    sched = ScoreboardScheduler()
    with MicroBatcher(_engine, microbatch=8, deadline_s=2.0,
                      n_features=N_FEAT, scheduler=sched) as mb:
        warm = [mb.submit(np.arange(N_FEAT), tier=BATCH) for _ in range(8)]
        for h in warm:
            h.result(timeout=5.0)
        hs = [mb.submit(np.full(N_FEAT, i, np.int32),
                        tier=interactive_tier(0.4)) for i in range(4)]
        for i, h in enumerate(hs):
            out = h.result(timeout=5.0)
            assert np.array_equal(
                out, _engine(np.full(N_FEAT, i, np.int32)[None])[0])
            assert h.t_done <= h.deadline_at + 0.35
    fills = [f.fill for f in mb.flushes[1:]]
    assert sum(fills) == 4
    assert max(fills) == 4       # coalesced, not four fill-1 flushes


# ---------------------------------------------------------------------------
# fill-normalized service estimation
# ---------------------------------------------------------------------------

def test_service_estimate_normalizes_by_fill():
    """The admission estimate prices the flush a request would RIDE:
    with (fill, seconds) history spanning fill sizes, the estimate for
    a lone straggler differs from a full batch by the fitted per-row
    cost, and stays conservative (never below the true line).
    (Regression: the estimator was fill-independent — a history of
    fill-1 stragglers priced a 32-row flush at straggler cost and vice
    versa.)"""
    sched = ScoreboardScheduler()
    a_true, b_true = 0.001, 0.002          # 1 ms overhead + 2 ms/row
    for fill in (1, 2, 4, 8, 1, 2, 4, 8):
        sched.note_service(a_true + b_true * fill, fill=fill)
    est1 = sched.service_estimate_s(fill=1)
    est8 = sched.service_estimate_s(fill=8)
    est16 = sched.service_estimate_s(fill=16)   # beyond observed fills
    blind = sched.service_estimate_s()
    # the fit recovers the slope: 7 rows apart => ~14 ms apart
    assert est8 - est1 == pytest.approx(7 * b_true, rel=0.15)
    assert est16 - est1 == pytest.approx(15 * b_true, rel=0.15)
    # conservative: residual pad keeps each estimate >= the true cost
    assert est1 >= a_true + b_true * 1 - 1e-12
    assert est8 >= a_true + b_true * 8 - 1e-12
    # fill-blind p90 sits inside the observed range — it cannot price
    # BOTH a straggler and a bigger-than-seen batch, which is the bug
    assert est1 < blind < est16


def test_service_estimate_degenerate_history_falls_back():
    """Too little history, a single distinct fill, or a noise-dominated
    fit (negative slope) must fall back to the fill-blind conservative
    p90 instead of extrapolating nonsense."""
    # fewer than 4 pairs -> p90
    s = ScoreboardScheduler()
    for fill in (1, 8):
        s.note_service(0.01, fill=fill)
    assert s.service_estimate_s(fill=4) == s.service_estimate_s()
    # one distinct fill -> p90 (no slope to fit)
    s = ScoreboardScheduler()
    for _ in range(8):
        s.note_service(0.01, fill=4)
    assert s.service_estimate_s(fill=32) == s.service_estimate_s()
    # negative slope (service shrinking with fill is noise) -> p90
    s = ScoreboardScheduler()
    for fill, sec in ((1, 0.020), (2, 0.015), (4, 0.010), (8, 0.005)):
        s.note_service(sec, fill=fill)
        s.note_service(sec, fill=fill)
    assert s.service_estimate_s(fill=8) == s.service_estimate_s()
    # fill-less history (legacy note_service callers) -> p90
    s = ScoreboardScheduler()
    for _ in range(8):
        s.note_service(0.01)
    assert s.service_estimate_s(fill=4) == s.service_estimate_s()
    # and no history at all stays None
    assert ScoreboardScheduler().service_estimate_s(fill=4) is None


# ---------------------------------------------------------------------------
# EDF issue under live backlog
# ---------------------------------------------------------------------------

def test_interactive_overtakes_batch_backlog():
    """With a best-effort backlog already queued, a late-arriving
    deadline-class request rides the NEXT flush — the scoreboard's
    whole reason to replace FIFO."""
    gate = threading.Event()
    seen = []

    def gated(batch):
        gate.wait(5.0)
        seen.append(np.array(batch))
        return _engine(batch)

    sched = ScoreboardScheduler()
    with MicroBatcher(gated, microbatch=4, deadline_s=0.01,
                      n_features=N_FEAT, scheduler=sched) as mb:
        # first flush issues (some prefix) and blocks at the gate;
        # everything submitted after piles into the scoreboard
        batch_hs = [mb.submit(np.full(N_FEAT, i, np.int32), tier=BATCH)
                    for i in range(10)]
        time.sleep(0.05)                  # first flush is at the gate
        vip = mb.submit(np.full(N_FEAT, 99, np.int32),
                        tier=interactive_tier(60.0))
        gate.set()
        vip_out = vip.result(timeout=10.0)
        for h in batch_hs:
            h.result(timeout=10.0)
    assert np.array_equal(vip_out,
                          _engine(np.full(N_FEAT, 99, np.int32)[None])[0])
    # the VIP row appears in the flush right after the gated one, ahead
    # of the queued best-effort overflow
    vip_flush = next(i for i, b in enumerate(seen) if 99 in b[:, 0])
    assert vip_flush <= 1
    later = {v for b in seen[vip_flush + 1:] for v in b[:, 0].tolist()}
    assert later & set(range(10))         # best-effort rows served after


# ---------------------------------------------------------------------------
# work-stealing
# ---------------------------------------------------------------------------

def test_steal_group_moves_overflow_to_idle_sibling():
    """A backlogged batcher's OVERFLOW (beyond one full microbatch) is
    executed on the idle sibling's thread with the victim's engine:
    results identical, flushes recorded on the VICTIM with cause
    "steal", group counters advance."""
    group = StealGroup()
    s_hot, s_idle = ScoreboardScheduler(), ScoreboardScheduler()

    def slow(batch):
        time.sleep(0.005)
        return _engine(batch)

    hot = MicroBatcher(slow, microbatch=4, deadline_s=0.001,
                       n_features=N_FEAT, scheduler=s_hot,
                       steal_group=group).start()
    idle = MicroBatcher(slow, microbatch=4, deadline_s=0.001,
                        n_features=N_FEAT, scheduler=s_idle,
                        steal_group=group).start()
    try:
        hs = [hot.submit(np.full(N_FEAT, i, np.int32), tier=BATCH)
              for i in range(64)]
        for i, h in enumerate(hs):
            out = h.result(timeout=30.0)
            assert np.array_equal(
                out, _engine(np.full(N_FEAT, i, np.int32)[None])[0])
    finally:
        hot.stop()
        idle.stop()
    assert group.steals >= 1
    assert group.stolen_requests >= 1
    stolen = [f for f in hot.flushes if f.cause == "steal"]
    assert stolen and sum(f.fill for f in stolen) == group.stolen_requests
    assert not [f for f in idle.flushes if f.cause == "steal"]
    # accounting: every request served exactly once, between the two
    assert sum(f.fill for f in hot.flushes) == 64


def test_steal_is_notification_driven_not_poll_driven():
    """A victim whose board goes steal-eligible NOTIFIES the group's
    idle batchers (StealGroup.notify_work from the submit path) — the
    idle sibling starts stealing on notification latency, not on the
    poll cadence.  Pinned by making the poll absurdly slow (30 s): if
    stealing still only happened on the timer, the idle sibling would
    sleep through the whole run and steals would be zero (the hot
    batcher alone finishes this backlog in well under 30 s)."""
    group = StealGroup()
    s_hot, s_idle = ScoreboardScheduler(), ScoreboardScheduler()

    def slow(batch):
        time.sleep(0.005)
        return _engine(batch)

    hot = MicroBatcher(slow, microbatch=4, deadline_s=0.001,
                       n_features=N_FEAT, scheduler=s_hot,
                       steal_group=group, steal_poll_s=30.0).start()
    idle = MicroBatcher(slow, microbatch=4, deadline_s=0.001,
                        n_features=N_FEAT, scheduler=s_idle,
                        steal_group=group, steal_poll_s=30.0).start()
    t0 = time.monotonic()
    try:
        hs = [hot.submit(np.full(N_FEAT, i, np.int32), tier=BATCH)
              for i in range(64)]
        for i, h in enumerate(hs):
            out = h.result(timeout=20.0)
            assert np.array_equal(
                out, _engine(np.full(N_FEAT, i, np.int32)[None])[0])
    finally:
        hot.stop()
        idle.stop()
    # finished far inside one poll period, with real steals — only the
    # notification path can have woken the idle sibling
    assert time.monotonic() - t0 < 25.0
    assert group.steals >= 1
    assert group.stolen_requests >= 1
    stolen = [f for f in hot.flushes if f.cause == "steal"]
    assert stolen and sum(f.fill for f in stolen) == group.stolen_requests
    assert sum(f.fill for f in hot.flushes) == 64


SPEC_KW = dict(in_features=16, widths=(24, 12, 5), bits=2, fan_in=3,
               degree=1, adder_width=2)


@functools.lru_cache(maxsize=None)
def _net(seed: int):
    spec = LD.ModelSpec(name=f"sched-{seed}", **SPEC_KW)
    return LS.synthesise(LD.init_model(jax.random.key(seed), spec), spec)


def _oracle(tables, rows: np.ndarray) -> np.ndarray:
    import jax.numpy as jnp
    codes = jnp.asarray(rows)
    for t in tables:
        codes = lg_ref.lut_layer(codes, t.conn, t.sub_table, t.add_table,
                                 t.in_bits, t.sub_bits)
    return np.asarray(codes)


def test_registry_work_stealing_between_models():
    """A hot model's backlog is partly served on the idle sibling
    model's batcher thread (same registry StealGroup), bit-exact vs
    the hot model's own oracle."""
    ta, tb = _net(0), _net(1)
    rows = np.random.default_rng(3).integers(0, 4, (96, 16)).astype(np.int32)
    want = _oracle(ta, rows)
    with ModelRegistry(microbatch=8, deadline_s=0.001,
                       slo_tiers=[interactive_tier(0.05), BATCH],
                       work_stealing=True) as reg:
        reg.register("hot", ta)
        reg.register("idle", tb)
        hs = [reg.submit("hot", r, tier=BATCH) for r in rows]
        for i, h in enumerate(hs):
            assert np.array_equal(h.result(timeout=30.0), want[i]), i
        steals = reg.steal_group.steals
        st = reg.stats()
    # the hot backlog (96 requests vs microbatch 8, sub-ms kernels)
    # must have triggered at least one steal, surfaced in stats too
    assert steals >= 1
    assert st["hot"]["steals"] == st["idle"]["steals"] == steals
    assert reg.steal_group.stolen_requests >= 1


def test_capacity_accounting_reports_live_estimates():
    ta = _net(0)
    rows = np.random.default_rng(3).integers(0, 4, (16, 16)).astype(np.int32)
    with ModelRegistry(microbatch=8, deadline_s=0.002,
                       slo_tiers=[interactive_tier(0.05), BATCH]) as reg:
        reg.register("m", ta)
        cap0 = reg.capacity("m")
        assert cap0["kernel_est_s"] is None        # no history yet
        assert reg.estimate_delay_s("m") is None
        hs = [reg.submit("m", r, tier=BATCH) for r in rows]
        for h in hs:
            h.result(timeout=10.0)
        cap = reg.capacity("m")
        assert cap["kernel_est_s"] > 0
        assert cap["est_delay_s"] >= cap["kernel_est_s"]
        assert cap["sustainable_req_s"] == pytest.approx(
            8 / cap["kernel_est_s"])
        assert cap["sheds"] == 0
        assert reg.estimate_delay_s("m") > 0
    assert reg.estimate_delay_s("gone") is None    # unknown id: no est


# ---------------------------------------------------------------------------
# the SLO-attainment harness (the acceptance contract)
# ---------------------------------------------------------------------------

def test_slo_attainment_under_overload():
    """Mixed 2-tier Poisson load well past the sustainable rate (the
    contract floor is 1.5x; this drives 2x so the backlog provably
    pins at the admission ceiling within the stream):
      * interactive deadline attainment >= 95% over ADMITTED requests,
      * every shed is the typed DeadlineUnmeetable (the driver records
        None exactly and only for those) — zero silent drops,
      * zero hung handles (every admitted handle completes),
      * batch-tier throughput >= 0.7x the FIFO baseline,
      * under overload the scheduler actually sheds (the admission
        path is exercised, not trivially idle).

    The INVARIANTS (typed sheds, zero silent drops, zero hung handles,
    exact accounting) are hard on every attempt.  The TIMING contracts
    (attainment, batch throughput) get one bounded retry: a single
    ~25 ms CI-machine stall while the queue sits at the admission
    ceiling converts the whole resident queue into misses, which no
    admission policy can prevent after the fact.  GC is paused over
    the timed phases for the same reason."""
    KERNEL_S = 0.008
    MICRO = 32
    sustainable = MICRO / KERNEL_S                 # ~4000 req/s
    rate = 2.0 * sustainable                       # ~8000 req/s offered
    n_req = 4800
    it = interactive_tier(0.030)
    pattern = [it, it, it, BATCH]                  # 75% deadline-class:
    # interactive alone offers ~6000 req/s > sustainable -> must shed
    rows = np.arange(n_req, dtype=np.int32)[:, None].repeat(N_FEAT, 1)

    def slow_engine(batch):
        time.sleep(KERNEL_S)
        return _engine(batch)

    def fifo_baseline():
        # same stream, same engine, no scheduler
        with MicroBatcher(slow_engine, microbatch=MICRO,
                          deadline_s=0.002, n_features=N_FEAT) as fifo:
            t0 = time.monotonic()
            fifo_hs = [fifo.submit(r) for r in rows]
            for h in fifo_hs:
                h.result(timeout=120.0)
            return time.monotonic() - t0

    def scheduled_run():
        sched = ScoreboardScheduler()
        with MicroBatcher(slow_engine, microbatch=MICRO,
                          deadline_s=0.002, n_features=N_FEAT,
                          scheduler=sched) as mb:
            replay = replay_tiered_open_loop(mb, rows, rate=rate,
                                             tiers=pattern, seed=7,
                                             timeout_s=120.0)
        report = tier_report(replay)
        inter, batch = report["interactive"], report["batch"]

        # HARD invariants — every attempt.  Zero silent drops: every
        # request is either a completed handle or a typed shed, and
        # the driver records None exactly and only for typed sheds.
        assert len(replay.handles) == n_req
        assert sum(1 for h in replay.handles if h is None) == replay.sheds
        # zero hung handles
        hung = [h for h in replay.handles if h is not None and not h.done]
        assert not hung
        # no engine failures in this harness: served accounting exact
        assert inter["served"] == inter["offered"] - inter["shed"]
        assert batch["served"] == batch["offered"]  # best-effort: no shed
        assert batch["shed"] == 0
        # overload really exercised admission
        assert replay.sheds > 0
        assert sched.sheds == replay.sheds
        assert inter["shed_rate"] < 0.5             # bounded, not collapse
        return report

    import gc
    gc.collect()
    gc.disable()
    try:
        fifo_span = fifo_baseline()
        n_batch_tier = sum(1 for i in range(n_req)
                           if pattern[i % len(pattern)] is BATCH)
        fifo_batch_tput = n_batch_tier / fifo_span

        report = None
        for attempt in range(2):
            report = scheduled_run()
            inter, batch = report["interactive"], report["batch"]
            if (inter["attainment"] >= 0.95
                    and batch["throughput_req_s"] >= 0.7 * fifo_batch_tput):
                break
    finally:
        gc.enable()

    # THE contract: p99 attainment of the interactive tier
    assert inter["attainment"] >= 0.95, report
    # batch tier keeps flowing: >= 0.7x the FIFO baseline throughput
    assert batch["throughput_req_s"] >= 0.7 * fifo_batch_tput, \
        (batch["throughput_req_s"], fifo_batch_tput)
