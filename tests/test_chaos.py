"""Chaos-schedule certification of the replicated dedup service
(``./test.sh --chaos``).

PR 8 certified the fault envelope with hand-picked single-failure scripts;
this suite certifies it with seeded randomized fault storms
(`train/fault.ChaosSchedule`): deterministic RNG-driven kill / revive /
slow / flaky sequences over batch ordinals, swept across replication
r in {1,2,3} x n_workers in {2,4,5} x both hash families. The schedule's
kill guard keeps at most ``replication - 1`` workers dead at once — the
envelope inside which the replicated shard plane promises **bit-identical
verdicts with zero recall loss** — and every storm here asserts exactly
that, batch by batch, against a fault-free in-process `MinHashDeduper`
oracle, then certifies post-storm state: every replica copy of every band
equal to the oracle's band after `finish()` revives and read-repairs.

The replica-hedging contracts ride along: a hedged probe must go to a
*different* replica (asserted on the submit seam), wins are attributed per
replica slot, the Watchdog straggler signal hedges proactively, and a
corrupt replica fails over without losing a verdict.
"""
import os

import numpy as np
import pytest

from repro.data.dedup import DedupConfig, MinHashDeduper, unpack_band
from repro.data.service import (DedupService, ServiceConfig, run_dedup_job)
from repro.train.fault import (ChaosSchedule, DataCorruption, ProbeTimeout,
                               SnapshotInterrupt, WorkerCrash)


def _cfg(**kw):
    base = dict(vocab=4096, n_signatures=32, lsh_bands=8, threshold=0.6)
    base.update(kw)
    return DedupConfig(**base)


def _docs(n=56, seed=3, dup_every=7):
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, 4096, size=int(m)).astype(np.int32)
            for m in rng.integers(30, 300, size=n)]
    for i in range(dup_every, n, dup_every):
        docs[i] = docs[i - 2].copy()
    return docs


# ---------------------------------------------------------------------------
# the schedule generator itself
# ---------------------------------------------------------------------------

def test_chaos_schedule_is_deterministic():
    kw = dict(replication=2, job_kill_rate=0.2, snapshot_interrupt_rate=0.2)
    a = ChaosSchedule(5, 40, 4, **kw)
    b = ChaosSchedule(5, 40, 4, **kw)
    assert a.events == b.events               # frozen dataclass equality
    assert a.injector_kinds == b.injector_kinds
    c = ChaosSchedule(6, 40, 4, **kw)
    assert a.events != c.events               # actually seed-dependent


def test_chaos_schedule_counts_census():
    s = ChaosSchedule(9, 60, 4, replication=2, job_kill_rate=0.15,
                      snapshot_interrupt_rate=0.15)
    c = s.counts()
    assert c["total"] == len(s.events) + len(s.injector_kinds)
    assert sum(c[a] for a in ("kill", "revive", "slow", "fast",
                              "flaky")) == len(s.events)
    assert c["snapshot_interrupts"] == sum(
        1 for k in s.injector_kinds.values() if k is SnapshotInterrupt)


@pytest.mark.parametrize("replication,n_workers", [(1, 4), (2, 4), (3, 5)])
def test_chaos_kill_guard_never_exceeds_envelope(replication, n_workers):
    """Replay every schedule's kill/revive bookkeeping: never more than
    replication-1 workers dead at once — with non-colocated placement
    that is precisely the zero-recall-loss envelope."""
    for seed in range(6):
        s = ChaosSchedule(seed, 50, n_workers, replication=replication)
        dead = set()
        for ev in s.events:
            if ev.action == "kill":
                dead.add(ev.worker)
            elif ev.action == "revive":
                dead.discard(ev.worker)
            assert len(dead) <= replication - 1, (seed, ev)


# ---------------------------------------------------------------------------
# the certification sweep: storms x replication x workers x hash family
# ---------------------------------------------------------------------------

STORMS = [
    # (seed, n_workers, replication, family)
    (0, 2, 1, "cyclic"),
    (1, 4, 1, "general"),
    (2, 2, 2, "cyclic"),
    (3, 4, 2, "general"),
    (4, 4, 2, "cyclic"),
    (5, 5, 2, "general"),
    (6, 4, 3, "cyclic"),
    (7, 5, 3, "general"),
    (8, 5, 3, "cyclic"),
    (9, 5, 2, "cyclic"),
    (10, 4, 3, "general"),
    (11, 2, 2, "general"),
]


@pytest.mark.parametrize("seed,n_workers,replication,family", STORMS)
def test_storm_bit_parity_and_zero_recall_loss(seed, n_workers, replication,
                                               family):
    """Under every guarded storm the service's verdicts are bit-identical
    to the fault-free oracle batch by batch; at r>=2 recall_loss stays
    exactly zero throughout; finish() (revive + read-repair) leaves every
    replica copy equal to the oracle's band state and the next fault-free
    batch still matches."""
    cfg = _cfg(family=family)
    docs = _docs(n=56, seed=100 + seed)
    sched = ChaosSchedule(seed, n_batches=6, n_workers=n_workers,
                          replication=replication)
    with MinHashDeduper(cfg) as ref, \
         DedupService(cfg, ServiceConfig(n_workers=n_workers,
                                         replication=replication,
                                         backoff_base_s=0.001)) as svc:
        for t in range(6):
            lo = t * 8
            sched.apply(svc, t)
            want = ref.add_batch(docs[lo:lo + 8])
            got = svc.add_batch(docs[lo:lo + 8])
            np.testing.assert_array_equal(
                got, want, err_msg=f"storm {seed} batch {t}")
            if svc.r >= 2:
                assert svc.telemetry()["recall_loss"] == 0.0, (seed, t)
        sched.finish(svc)
        tele = svc.telemetry()
        assert tele["recall_loss"] == 0.0
        assert tele["dead_replicas"] == 0
        assert tele["repair_queue_pairs"] == 0
        assert tele["dropped_inserts"] == 0
        # post-storm certification: every replica copy == the oracle band
        ref_index = ref.export_state()["index"]
        for b in range(svc.n_bands):
            want_band = unpack_band(ref_index[f"band_{b:04d}"])
            for w in svc.replica_workers(b):
                assert w.shards[b] == want_band, (seed, b, w.worker_id)
        # and the service keeps matching after the storm
        np.testing.assert_array_equal(svc.add_batch(docs[48:]),
                                      ref.add_batch(docs[48:]))


@pytest.mark.parametrize("victim", [0, 1, 2, 3])
def test_single_worker_kill_r2_zero_recall_loss(victim):
    """The acceptance headline: with replication=2, killing ANY single
    worker mid-job keeps verdicts bit-identical to the all-live service —
    recall_loss == 0, nothing skipped, nothing dropped."""
    docs = _docs(n=48, seed=33)
    with MinHashDeduper(_cfg()) as ref, \
         DedupService(_cfg(), ServiceConfig(n_workers=4, replication=2,
                                            backoff_base_s=0.001)) as svc:
        for t, lo in enumerate(range(0, 48, 8)):
            if t == 2:
                svc.kill_worker(victim)
            want = ref.add_batch(docs[lo:lo + 8])
            got = svc.add_batch(docs[lo:lo + 8])
            np.testing.assert_array_equal(got, want, err_msg=f"batch {t}")
        tele = svc.telemetry()
    assert tele["recall_loss"] == 0.0
    assert tele["skipped_probes"] == 0
    assert tele["dropped_inserts"] == 0
    assert tele["queued_inserts"] > 0        # the dead replicas' share
    assert tele["lost_bands"] == 0
    assert tele["dead_replicas"] == svc.n_bands * 2 // 4   # victim's share


# ---------------------------------------------------------------------------
# replica hedging contracts
# ---------------------------------------------------------------------------

def test_hedge_targets_a_different_replica_and_wins_are_attributed():
    """Every hedged probe must go to a different worker than the first
    attempt (the next live replica — a straggler cannot slow its own
    hedge), and hedge_wins decompose exactly into the per-replica-slot
    attribution telemetry reports."""
    docs = _docs(n=16, seed=5)
    with MinHashDeduper(_cfg()) as ref:
        want = ref.add_batch(docs)
    with DedupService(_cfg(), ServiceConfig(n_workers=4, replication=2,
                                            hedge_after_s=0.01,
                                            probe_timeout_s=5.0)) as svc:
        calls = []
        orig = svc._submit

        def spy(worker, op, band, *args):
            calls.append((op, band, worker.worker_id))
            return orig(worker, op, band, *args)

        svc._submit = spy
        svc.workers[0].delay_s = 0.08        # primary of bands 0 and 4
        got = svc.add_batch(docs)
        tele = svc.telemetry()
    np.testing.assert_array_equal(got, want)
    assert tele["hedges"] >= 1
    assert tele["hedge_wins"] >= 1
    assert tele["retries"] == 0
    assert tele["lost_bands"] == 0
    # decomposition: wins sum to the per-slot attribution
    assert sum(tele[f"hedge_wins_replica_{j}"]
               for j in range(2)) == tele["hedge_wins"]
    # hedged pairs target distinct workers, both legal replicas of the band
    per_band = {}
    for op, band, wid in calls:
        if op == "probe":
            per_band.setdefault(band, []).append(wid)
    hedged = {b: ws for b, ws in per_band.items() if len(ws) > 1}
    assert hedged                             # the straggler forced hedges
    for b, ws in hedged.items():
        legal = {w.worker_id for w in svc.replica_workers(b)}
        assert len(set(ws)) == len(ws), (b, ws)      # never the same worker
        assert set(ws) <= legal, (b, ws, legal)


def test_watchdog_slow_signal_triggers_proactive_hedge():
    """Once the per-worker latency Watchdog flags a straggler, hedges fire
    immediately (before hedge_after_s), and verdicts still match.

    The straggler signal is driven by call counts, not by wall time:
    worker 1's Watchdog still records every completed call's latency in
    its real windowed history, but its verdict is a breach on every call
    after its warm-up. hedge_after_s is far beyond any call, so every
    hedge in the run is a proactive one. (The median + MAD verdict itself
    is tested on counts in test_train.)"""
    docs = _docs(n=32, seed=8)
    with MinHashDeduper(_cfg()) as ref, \
         DedupService(_cfg(), ServiceConfig(n_workers=4, replication=2,
                                            hedge_after_s=30.0,
                                            watchdog_warmup=4,
                                            watchdog_window=8)) as svc:
        wd = svc._wd[1]
        real_observe = wd.observe

        def observe(dt, step):
            real_observe(dt, step)
            return len(wd.times) > wd.warmup

        wd.observe = observe
        want0 = ref.add_batch(docs[:16])
        got0 = svc.add_batch(docs[:16])       # worker 1 passes its warm-up
        want1 = ref.add_batch(docs[16:])
        got1 = np.concatenate([svc.add_batch(docs[16:24]),
                               svc.add_batch(docs[24:])])
        tele = svc.telemetry()
    np.testing.assert_array_equal(got0, want0)
    np.testing.assert_array_equal(got1, want1)
    assert len(wd.times) == wd.window       # the history was trimmed
    assert tele["proactive_hedges"] >= 1
    assert tele["hedges"] == tele["proactive_hedges"]
    assert tele["lost_bands"] == 0


def test_corrupt_replica_fails_over_without_losing_a_verdict():
    """DataCorruption is fatal for the replica (no retry against the same
    bytes — immediate strike-out) but not for the probe: it fails over to
    a clean peer and the verdicts stay bit-identical; revive read-repairs
    the corrupt copy back."""
    docs = _docs(n=32, seed=13)
    with MinHashDeduper(_cfg()) as ref, \
         DedupService(_cfg(), ServiceConfig(n_workers=4, replication=2,
                                            backoff_base_s=0.001)) as svc:
        want0 = ref.add_batch(docs[:16])
        got0 = svc.add_batch(docs[:16])
        svc.replica_workers(0)[0].fail_next.append(DataCorruption)
        want1 = ref.add_batch(docs[16:])
        got1 = svc.add_batch(docs[16:])
        tele = svc.telemetry()
        assert tele["dead_replicas"] == 1     # fatal strike, immediately
        assert tele["recall_loss"] == 0.0
        svc.revive()
        assert svc.telemetry()["dead_replicas"] == 0
    np.testing.assert_array_equal(got0, want0)
    np.testing.assert_array_equal(got1, want1)


# ---------------------------------------------------------------------------
# job-level chaos: storms + loop kills + snapshot interrupts
# ---------------------------------------------------------------------------

def test_job_under_chaos_with_injector_faults_is_bit_identical(tmp_path):
    """run_dedup_job under a schedule that also kills the job loop and
    interrupts snapshots: the recovery loop restores the latest atomic
    snapshot, replays (re-applying the replayed batches' worker events),
    and the final flags are bit-identical to the fault-free batch loop."""
    docs = _docs(n=40, seed=77)
    with MinHashDeduper(_cfg()) as ref:
        want = np.concatenate(
            [ref.add_batch(docs[lo:lo + 8]) for lo in range(0, 40, 8)])
    sched = ChaosSchedule(21, n_batches=5, n_workers=4, replication=2,
                          job_kill_rate=0.4, snapshot_interrupt_rate=0.3)
    assert sched.injector_kinds                # this seed does kill the job
    with DedupService(_cfg(), ServiceConfig(n_workers=4, replication=2,
                                            backoff_base_s=0.001)) as svc:
        res = run_dedup_job(svc, docs, directory=str(tmp_path),
                            batch_docs=8, snapshot_every=1, chaos=sched)
        tele = svc.telemetry()
    np.testing.assert_array_equal(res["flags"], want)
    assert res["restarts"] >= 1
    assert tele["resumes"] >= 1
    assert tele["recall_loss"] == 0.0
    assert not any(x.endswith(".tmp") for x in os.listdir(tmp_path))


def test_job_rejects_chaos_and_injector_together(tmp_path):
    sched = ChaosSchedule(0, 2, 2)
    with DedupService(_cfg(), ServiceConfig(n_workers=2)) as svc:
        with pytest.raises(ValueError, match="chaos"):
            run_dedup_job(svc, _docs(n=8), directory=str(tmp_path),
                          chaos=sched, injector=sched.as_injector())
