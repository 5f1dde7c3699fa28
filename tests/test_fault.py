"""Fault-injection + failure-domain suite (PR-9).

Pins the hardening contracts of :mod:`repro.fault` and the layers it
exercises:

  * the error taxonomy: retryable flags, ``retry_after`` hints, and the
    guarantee that every serving failure is a *typed*
    :class:`~repro.fault.errors.FaultError` (bare RuntimeErrors are a
    contract breach the chaos driver also polices);
  * :class:`~repro.fault.inject.FaultPlan` determinism (a plan is a
    pure function of its seed) and the filesystem shims: EIO / ENOSPC /
    torn-write injection on the WAL with ``repair_tail`` recovering the
    valid prefix;
  * the durable store's DEGRADED state machine: a WAL fault flips
    writes to typed ``Unavailable(retry_after)`` while reads keep
    serving the committed snapshot; probes re-attach when the disk
    heals; a client with retries rides the whole window through and
    the store never loses or double-applies an acked chunk;
  * ``GraphClient`` retry policy: bounded backoff honoring
    ``retry_after``, ``DeadlineExceeded`` on budget exhaustion,
    non-retryable errors surfacing immediately, and (session, seq)
    idempotent resubmit;
  * failure-path shutdown ordering: broker/replica-set stops release
    every parked gen-waiter with a typed error -- no hangs, no bare
    RuntimeError -- and in-flight ReplicaSet queries fail over to a
    healthy peer;
  * the LogTailer-vs-trim window: a segment vanishing underneath the
    cursor (poll or constructor) is a typed resync signal
    (``WalTrimmed``), which :meth:`Replica.tail_once` absorbs as a
    snapshot fast-forward, never an exception.
"""
import os
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.api import AddEdge, Consistency, GraphClient, SameSCC
from repro.ckpt import oplog
from repro.ckpt.durable import DEGRADED, HEALTHY, DurableService, wal_dir
from repro.core import graph_state as gs
from repro.core.broker import QueryBroker
from repro.core.replicas import Replica, ReplicaSet
from repro.core.service import SCCService
from repro.fault import errors as fault_errors
from repro.fault.inject import (FaultPlan, FsFault, ReplicaKill, Stall,
                                fire_kills, injected)

NV = 24
KNOBS = dict(buckets=(8,), proactive_grow=True)


def tiny_cfg():
    return gs.GraphConfig(n_vertices=NV, edge_capacity=64, max_probes=16,
                          max_outer=NV + 1, max_inner=NV + 2)


def make_writer(directory, **durable_kw):
    cfg = tiny_cfg()
    durable_kw.setdefault("snapshot_every", 0)
    durable_kw.setdefault("recover_probe_s", 0.0)
    return DurableService(cfg, str(directory),
                          state=gs.all_singletons(cfg), sync_every=1,
                          **durable_kw, **KNOBS)


def chunk(rng, n=8):
    return (rng.integers(2, 4, n).astype(np.int32),
            rng.integers(0, NV, n).astype(np.int32),
            rng.integers(0, NV, n).astype(np.int32))


def leaves_equal(a, b):
    import jax
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ------------------------------------------------------------ taxonomy ---


def test_taxonomy_retryable_flags_and_hierarchy():
    from repro.tenancy.queue import QueueFull

    assert not fault_errors.FaultError("x").retryable
    assert fault_errors.Unavailable("x").retryable
    assert QueueFull(0.1).retryable
    for klass in (fault_errors.DeadlineExceeded,
                  fault_errors.BrokerStopped,
                  fault_errors.CapacityExhausted, fault_errors.WalGap,
                  fault_errors.WalTrimmed, fault_errors.WalCorrupt):
        e = klass("x")
        assert not e.retryable, klass
        assert isinstance(e, fault_errors.FaultError)
        assert isinstance(e, RuntimeError)  # compat: old callers keep
        #                                      catching RuntimeError
    assert issubclass(QueueFull, fault_errors.Unavailable)
    e = fault_errors.Unavailable("busy", retry_after=0.25)
    assert e.retry_after == 0.25
    assert fault_errors.Unavailable("busy").retry_after is None


# ----------------------------------------------------------- fault plan ---


def test_fault_plan_is_a_pure_function_of_seed():
    for profile in ("disk-fault", "replica-kill", "mixed"):
        a = FaultPlan.generate(7, profile, replicas=3, horizon_gens=48)
        b = FaultPlan.generate(7, profile, replicas=3, horizon_gens=48)
        assert a.events == b.events
    plans = [FaultPlan.generate(s, "mixed").events for s in range(8)]
    assert len(set(plans)) > 1  # seeds actually vary the schedule
    mixed = FaultPlan.generate(3, "mixed", replicas=2)
    assert mixed.fs and mixed.kills  # both domains scheduled
    disk = FaultPlan.generate(3, "disk-fault")
    assert disk.fs and not disk.kills
    kills = FaultPlan.generate(3, "replica-kill")
    assert kills.kills and not kills.fs


def test_fault_plan_counts_calls_per_op_and_match():
    plan = FaultPlan(fs=(FsFault("write", "wal", first=2, count=1),))
    path = "/store/wal/wal_00000001.seg"
    assert plan.check_fs("write", path) is None  # call 0
    assert plan.check_fs("fsync", path) is None  # other op: no tick
    assert plan.check_fs("write", path) is None  # call 1
    assert plan.check_fs("write", path) is not None  # call 2: in window
    assert plan.check_fs("write", path) is None  # window passed
    assert plan.check_fs("write", "/elsewhere/data.bin") is None


def test_fs_injection_eio_enospc_and_torn(tmp_path):
    d = str(tmp_path / "seg")
    w = oplog.OpLogWriter(d, sync_every=1)
    k, u, v = (np.zeros(2, np.int32),) * 3
    w.append(0, k, u, v)

    plan = FaultPlan(fs=(FsFault("write", "seg", first=0, count=1,
                                 error="enospc"),))
    with injected(plan):
        with pytest.raises(OSError) as ei:
            w.append(1, k, u, v)
        assert ei.value.errno == 28  # ENOSPC
        assert plan.triggered and plan.triggered[0][1] == "enospc"
    w.discard_tail()

    # torn write: a prefix of the record lands, then EIO -- append
    # rolls its own torn bytes back, so the log is already clean on
    # disk and repair_tail finds nothing left to drop
    plan = FaultPlan(fs=(FsFault("write", "seg", first=0, count=1,
                                 error="torn", tear_frac=0.5),))
    with injected(plan):
        with pytest.raises(OSError) as ei:
            w.append(1, k, u, v)
        assert ei.value.errno == 5  # EIO
    w.close()
    records, clean, _ = oplog.read_segment(
        oplog.list_segments(d)[-1][1])
    assert [r.gen_before for r in records] == [0]  # torn bytes invisible
    assert clean  # append truncated its own torn bytes
    assert oplog.repair_tail(d) == 0


def test_fsync_injection_hits_oplog_sync(tmp_path):
    d = str(tmp_path / "seg")
    w = oplog.OpLogWriter(d, sync_every=100)  # batch so sync() has work
    k, u, v = (np.zeros(2, np.int32),) * 3
    w.append(0, k, u, v)
    plan = FaultPlan(fs=(FsFault("fsync", "seg", first=0, count=1),))
    with injected(plan):
        with pytest.raises(OSError):
            w.sync()


def test_failed_append_rolls_back_its_own_record(tmp_path):
    # the fsync embedded in append() fails AFTER the record's bytes are
    # fully written: the never-acknowledged record must not survive on
    # disk (recovery would replay it ahead of a different chunk later
    # logged at the same generation)
    d = str(tmp_path / "seg")
    w = oplog.OpLogWriter(d, sync_every=1)
    k, u, v = (np.zeros(2, np.int32),) * 3
    w.append(0, k, u, v)
    plan = FaultPlan(fs=(FsFault("fsync", "seg", first=0, count=1),))
    with injected(plan):
        with pytest.raises(OSError):
            w.append(1, k, u, v)
    w.close()
    assert [r.gen_before for r in oplog.read_log(d)] == [0]


def test_drop_unapplied_tail_removes_unacked_records(tmp_path):
    d = str(tmp_path / "seg")
    w = oplog.OpLogWriter(d, sync_every=1)
    k, u, v = (np.zeros(2, np.int32),) * 3
    w.append(0, k, u, v)  # applied: the writer advanced to gen 1
    w.append(1, k, u, v)  # applied: gen 2
    w.append(2, k, u, v)  # a failed append whose rollback missed disk
    w.close()
    assert oplog.drop_unapplied_tail(d, 2) > 0
    assert [r.gen_before for r in oplog.read_log(d)] == [0, 1]
    assert oplog.drop_unapplied_tail(d, 2) == 0  # idempotent


# ------------------------------------------------------- degraded mode ---


def test_degraded_store_keeps_reads_and_recovers(tmp_path):
    svc = make_writer(tmp_path)
    rng = np.random.default_rng(0)
    svc._apply_ops(*chunk(rng))
    gen0, state0 = svc.gen, svc.state

    plan = FaultPlan(fs=(FsFault("write", "wal", first=0, count=2),))
    with injected(plan):
        with pytest.raises(fault_errors.Unavailable) as ei:
            svc._apply_ops(*chunk(rng))
        assert ei.value.retry_after is not None
        assert svc.health == DEGRADED
        assert svc.gen == gen0  # nothing applied
        # reads keep answering from the committed snapshot
        broker = QueryBroker(svc, buckets=(8,))
        fut = broker.submit("same_scc", [0, 1], [1, 2])
        assert broker.resolve(fut).gen == gen0
        # while degraded, updates bounce with typed Unavailable
        with pytest.raises(fault_errors.Unavailable):
            svc._apply_ops(*chunk(rng))
        assert svc.unavailable_rejects >= 1
    # plan disarmed = disk healed: the next update probes and succeeds
    ok, gen = svc._apply_ops(*chunk(rng))
    assert svc.health == HEALTHY and gen == gen0 + 1
    assert svc.degraded_count == 1 and svc.recovered_count == 1
    assert leaves_equal(state0, state0)
    svc.close()
    # acked history (and nothing else) survives on disk
    reopened = DurableService.open(str(tmp_path))
    assert reopened.gen == gen
    assert leaves_equal(reopened.state, svc.state)
    reopened.close()


def test_degraded_window_with_retrying_client_loses_nothing(tmp_path):
    svc = make_writer(tmp_path)
    client = GraphClient(svc, max_retries=16, backoff_base_s=0.001,
                         backoff_cap_s=0.01)
    oracle = SCCService(tiny_cfg(), state=gs.all_singletons(tiny_cfg()),
                        **KNOBS)
    ops = [AddEdge(int(a), int((a * 5 + 1) % NV)) for a in range(12)]
    plan = FaultPlan(fs=(FsFault("write", "wal", first=2, count=3),
                         FsFault("fsync", "wal", first=4, count=2)))
    with injected(plan):
        for op in ops:
            client.submit_many([op])  # retries ride out the window
    assert plan.triggered  # the faults really fired
    assert svc.degraded_count >= 1 and svc.health == HEALTHY
    assert client.retries >= 1
    for op in ops:
        oracle._apply_ops(*_encode_one(op))
    assert svc.gen == oracle.gen
    assert leaves_equal(svc.state, oracle.state)
    svc.close()
    reopened = DurableService.open(str(tmp_path))
    assert reopened.gen == oracle.gen
    assert leaves_equal(reopened.state, oracle.state)
    reopened.close()


def test_abandoned_failed_chunk_never_resurrects(tmp_path):
    """A chunk whose WAL append fails and which the client then gives
    up on (no retry) must not shadow a *different* chunk later logged
    at the same generation -- neither on recovery nor for replicas."""
    svc = make_writer(tmp_path)
    rng = np.random.default_rng(7)
    svc._apply_ops(*chunk(rng))
    gen0 = svc.gen
    chunk_a = chunk(rng)  # will fail; the client never retries it
    plan = FaultPlan(fs=(FsFault("fsync", "wal", first=0, count=1),))
    with injected(plan):
        with pytest.raises(fault_errors.Unavailable):
            svc._apply_ops(*chunk_a)  # fully written, fsync fails
    assert svc.gen == gen0
    chunk_b = chunk(rng)  # a DIFFERENT chunk, acked at the same gen
    ok, gen1 = svc._apply_ops(*chunk_b)
    assert gen1 > gen0 and svc.health == HEALTHY
    svc.close()
    reopened = DurableService.open(str(tmp_path))
    assert reopened.gen == gen1  # replayed B, never A
    assert leaves_equal(reopened.state, svc.state)
    reopened.close()


def test_attach_drops_failed_record_when_rollback_missed_disk(
        tmp_path, monkeypatch):
    """Belt-and-suspenders: even when append's own rollback cannot
    reach the sick disk, the re-attach probe truncates the
    valid-but-unapplied record before reopening the log."""
    svc = make_writer(tmp_path)
    rng = np.random.default_rng(8)
    svc._apply_ops(*chunk(rng))
    gen0 = svc.gen

    def no_disk(self, pos):  # rollback loses the race with the disk:
        self._pos = pos      # only the bookkeeping resets
        self._last_span = None
        self._unsynced = 0

    monkeypatch.setattr(oplog.OpLogWriter, "_discard_to", no_disk)
    plan = FaultPlan(fs=(FsFault("fsync", "wal", first=0, count=1),))
    with injected(plan):
        with pytest.raises(fault_errors.Unavailable):
            svc._apply_ops(*chunk(rng))  # record bytes survive on disk
    monkeypatch.undo()
    assert svc.gen == gen0
    recs = oplog.read_log(wal_dir(str(tmp_path)))
    assert recs and recs[-1].gen_before == gen0  # orphan really there
    ok, gen1 = svc._apply_ops(*chunk(rng))  # probe re-attaches + drops
    assert gen1 > gen0 and svc.health == HEALTHY
    svc.close()
    reopened = DurableService.open(str(tmp_path))
    assert reopened.gen == gen1
    assert leaves_equal(reopened.state, svc.state)
    reopened.close()


def _encode_one(op):
    from repro.api.ops import encode_updates
    return encode_updates([op])


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnraisableExceptionWarning")
def test_snapshot_failure_degrades_cadence_not_serving(tmp_path):
    # (np.savez's ZipFile.__del__ complains after the injected tear
    # closed its file mid-write -- expected debris of this fault)
    svc = make_writer(tmp_path / "store", snapshot_every=1)
    rng = np.random.default_rng(1)
    plan = FaultPlan(fs=(FsFault("write", "ckpt_", first=0, count=50),))
    with injected(plan):
        svc._apply_ops(*chunk(rng))  # commit is acked...
        for _ in range(50):  # ...even though its snapshot kick fails
            if svc.snapshot_failures:
                break
            time.sleep(0.02)
    assert svc.snapshot_failures >= 1
    assert svc.health == HEALTHY  # snapshot misses never block serving
    ok, gen = svc._apply_ops(*chunk(rng))
    svc.close()
    reopened = DurableService.open(str(tmp_path / "store"))
    assert reopened.gen == gen  # WAL still covers every commit
    reopened.close()


# ------------------------------------------------------- client retries ---


class _FlakyService:
    """Service stub: fails the first ``n_fail`` update chunks."""

    def __init__(self, n_fail, error=None):
        self.gen = 0
        self.n_fail = n_fail
        self.error = error or fault_errors.Unavailable(
            "transient", retry_after=0.002)
        self.attempts = 0

    def _apply_ops(self, kind, u, v, *, session=None, seq=None):
        self.attempts += 1
        if self.attempts <= self.n_fail:
            raise self.error
        self.gen += 1
        return np.ones(len(kind), bool), self.gen


def test_client_retries_transient_unavailable():
    svc = _FlakyService(3)
    client = GraphClient(svc, max_retries=8, backoff_base_s=0.001,
                         backoff_cap_s=0.004)
    res = client.submit_many([AddEdge(0, 1)])
    assert res[0].gen == 1 and svc.attempts == 4
    assert client.retries == 3
    assert client.token == 1  # RYW token advanced on the final success


def test_client_retry_exhaustion_reraises_the_typed_error():
    svc = _FlakyService(100)
    client = GraphClient(svc, max_retries=3, backoff_base_s=0.001,
                         backoff_cap_s=0.002)
    with pytest.raises(fault_errors.Unavailable):
        client.submit_many([AddEdge(0, 1)])
    assert svc.attempts == 4  # 1 + max_retries


def test_client_deadline_exceeded_is_typed_and_chains():
    svc = _FlakyService(100)
    client = GraphClient(svc, deadline_s=0.02, max_retries=1000,
                         backoff_base_s=0.005, backoff_cap_s=0.01)
    with pytest.raises(fault_errors.DeadlineExceeded) as ei:
        client.submit_many([AddEdge(0, 1)])
    assert isinstance(ei.value.__cause__, fault_errors.Unavailable)
    assert client.deadline_failures == 1


def test_client_does_not_retry_non_retryable_faults():
    svc = _FlakyService(100,
                        error=fault_errors.CapacityExhausted("full"))
    client = GraphClient(svc, max_retries=8)
    with pytest.raises(fault_errors.CapacityExhausted):
        client.submit_many([AddEdge(0, 1)])
    assert svc.attempts == 1  # no blind retries of deterministic errors


def test_client_honors_retry_after_hint():
    svc = _FlakyService(1, error=fault_errors.Unavailable(
        "wait", retry_after=0.05))
    client = GraphClient(svc, max_retries=2, backoff_base_s=0.0001,
                         backoff_cap_s=1.0)
    t0 = time.monotonic()
    client.submit_many([AddEdge(0, 1)])
    assert time.monotonic() - t0 >= 0.045  # waited the server hint


def test_idempotent_resubmit_dedups_on_session_seq():
    cfg = tiny_cfg()
    svc = SCCService(cfg, state=gs.all_singletons(cfg), **KNOBS)
    k, u, v = _encode_one(AddEdge(1, 2))
    ok1, gen1 = svc._apply_ops(k, u, v, session="s1", seq=1)
    # a retried chunk (same session+seq) returns the recorded ack and
    # does NOT advance the generation (never double-applied)
    ok2, gen2 = svc._apply_ops(k, u, v, session="s1", seq=1)
    assert gen2 == gen1 and np.array_equal(ok1, ok2)
    assert svc.deduped_resubmits == 1
    # a new seq (or another session) applies normally
    _, gen3 = svc._apply_ops(k, u, v, session="s1", seq=2)
    assert gen3 == gen1 + 1
    _, gen4 = svc._apply_ops(k, u, v, session="s2", seq=2)
    assert gen4 == gen3 + 1
    assert svc.stats()["deduped_resubmits"] == 1


# ------------------------------------------- shutdown / waiter release ---


@settings(max_examples=8)
@given(st.integers(1, 4), st.integers(1, 3))
def test_broker_stop_releases_parked_gen_waiters_typed(n_waiters,
                                                       extra_gen):
    cfg = tiny_cfg()
    svc = SCCService(cfg, state=gs.all_singletons(cfg), **KNOBS)
    broker = QueryBroker(svc, buckets=(8,))
    broker.start()
    results: list = []
    floor = svc.gen + extra_gen  # a generation that never commits

    def waiter():
        fut = broker.submit("same_scc", [0], [1], min_gen=floor)
        try:
            results.append(broker.resolve(fut, min_gen=floor))
        except BaseException as e:
            results.append(e)

    threads = [threading.Thread(target=waiter) for _ in range(n_waiters)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 5.0
    while broker.stats()["gen_waits"] < n_waiters and \
            time.monotonic() < deadline:
        time.sleep(0.002)
    broker.stop()
    for t in threads:
        t.join(timeout=5.0)
        assert not t.is_alive(), "parked waiter hung across stop()"
    assert len(results) == n_waiters
    for r in results:
        assert type(r) is fault_errors.BrokerStopped, r


def test_broker_resolve_timeout_raises_deadline_exceeded():
    cfg = tiny_cfg()
    svc = SCCService(cfg, state=gs.all_singletons(cfg), **KNOBS)
    broker = QueryBroker(svc, buckets=(8,))
    broker.start()
    fut = broker.submit("same_scc", [0], [1], min_gen=svc.gen + 10)
    with pytest.raises(fault_errors.DeadlineExceeded):
        broker.resolve(fut, min_gen=svc.gen + 10, timeout=0.05)
    broker.stop()


def test_broker_inline_resolve_deadline_is_tight():
    # inline mode (no dispatcher): the internal gen-wait slices must be
    # clamped to the remaining deadline, not overshoot it by ~0.5s
    cfg = tiny_cfg()
    svc = SCCService(cfg, state=gs.all_singletons(cfg), **KNOBS)
    broker = QueryBroker(svc, buckets=(8,))
    fut = broker.submit("same_scc", [0], [1], min_gen=svc.gen + 10)
    t0 = time.monotonic()
    with pytest.raises(fault_errors.DeadlineExceeded):
        broker.resolve(fut, min_gen=svc.gen + 10, timeout=0.05)
    assert time.monotonic() - t0 < 0.3


def test_queue_full_and_ticket_timeout_are_typed():
    from repro.tenancy.queue import QueueFull, WorkQueue

    def flush(batch):
        return {tid: (np.ones(len(k), bool), 1) for tid, k, u, v in batch}

    q = WorkQueue(flush, max_pending_ops=4, coalesce_ops=64,
                  flush_deadline_s=0.2)
    k, u, v = (np.zeros(5, np.int32),) * 3
    with pytest.raises(QueueFull) as ei:
        q.submit("t0", k, u, v)  # 5 ops > 4-op budget: immediate bounce
    assert ei.value.retryable and ei.value.retry_after is not None
    assert isinstance(ei.value, fault_errors.Unavailable)
    assert q.rejects == 1

    # ticket timeout: a non-leader waiter whose wave has not flushed yet
    # surfaces the typed DeadlineExceeded, not a bare hang
    k1 = np.zeros(1, np.int32)
    leader = threading.Thread(target=lambda: q.submit("t0", k1, k1, k1))
    leader.start()
    time.sleep(0.05)  # leadership taken, parked on the flush deadline
    with pytest.raises(fault_errors.DeadlineExceeded):
        q.submit("t1", k1, k1, k1, timeout=0.01)
    leader.join(timeout=5.0)
    assert not leader.is_alive()


# -------------------------------------------------- replica set faults ---


def _replicated(tmp_path, n=2, **rset_kw):
    svc = make_writer(tmp_path)
    rng = np.random.default_rng(2)
    svc._apply_ops(*chunk(rng))
    rset = ReplicaSet(str(tmp_path), n, query_buckets=(8,),
                      auto_tail=False, **rset_kw)
    for r in rset.replicas:
        while r.tail_once():
            pass
    return svc, rset


def test_replica_kill_flips_health_and_routing(tmp_path):
    svc, rset = _replicated(tmp_path)
    assert len(rset.healthy_replicas) == 2
    rset.replicas[0].kill()
    assert not rset.replicas[0].healthy
    assert rset.healthy_replicas == [rset.replicas[1]]
    for _ in range(4):  # all routing lands on the survivor
        fut = rset.submit("same_scc", [0], [1])
        assert rset._owner[fut][0] is rset.replicas[1]
        rset.resolve(fut)
    svc.close()


def test_no_healthy_replica_raises_unavailable_with_hint(tmp_path):
    svc, rset = _replicated(tmp_path)
    for r in rset.replicas:
        r.kill()
    with pytest.raises(fault_errors.Unavailable) as ei:
        rset.submit("same_scc", [0], [1])
    assert ei.value.retryable and ei.value.retry_after > 0
    svc.close()


def test_in_flight_query_fails_over_to_healthy_peer(tmp_path):
    svc, rset = _replicated(tmp_path)
    fut = rset.submit("same_scc", [0], [1])
    owner = rset._owner[fut][0]
    owner.kill()  # dies mid-flight: broker releases fut typed
    snap = rset.resolve(fut)  # transparently resubmitted + answered
    assert snap.gen >= 1
    assert rset.failovers == 1
    svc.close()


def test_replica_set_stop_mid_failover_releases_waiters_typed(tmp_path):
    svc, rset = _replicated(tmp_path)
    floor = svc.gen + 5  # never commits
    fut = rset.submit("same_scc", [0], [1], min_gen=floor)
    results: list = []

    def waiter():
        try:
            results.append(rset.resolve(fut, min_gen=floor))
        except BaseException as e:
            results.append(e)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    rset.stop()
    t.join(timeout=5.0)
    assert not t.is_alive(), "rset.stop() left a resolve hanging"
    assert len(results) == 1
    assert isinstance(results[0], fault_errors.FaultError), results[0]
    # stopped set refuses new work with the typed stop error
    with pytest.raises(fault_errors.BrokerStopped):
        rset.submit("same_scc", [0], [1])
    svc.close()


def test_supervisor_restarts_killed_replica(tmp_path):
    svc = make_writer(tmp_path)
    rng = np.random.default_rng(3)
    svc._apply_ops(*chunk(rng))
    rset = ReplicaSet(str(tmp_path), 2, query_buckets=(8,),
                      poll_interval=0.01, supervise=True,
                      health_check_s=0.02)
    try:
        victim = rset.replicas[0]
        victim.kill()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if rset.restarts >= 1 and len(rset.healthy_replicas) == 2:
                break
            time.sleep(0.01)
        assert rset.restarts >= 1, "supervisor never restarted the kill"
        assert rset.replicas[0] is not victim  # fresh snapshot boot
        assert rset.quarantined >= 1
        # the replacement serves: converges to the writer's gen
        rset.wait_all_for_gen(svc.gen, timeout=5.0)
        fut = rset.submit("same_scc", [0], [1], min_gen=svc.gen)
        assert rset.resolve(fut, min_gen=svc.gen).gen >= svc.gen
    finally:
        rset.stop()
        svc.close()


def test_supervisor_quarantines_dead_replica_once_only(tmp_path):
    # with the restart budget exhausted, a replica that stays dead must
    # not be re-shutdown and re-counted on every supervisor sweep
    svc = make_writer(tmp_path)
    rng = np.random.default_rng(9)
    svc._apply_ops(*chunk(rng))
    rset = ReplicaSet(str(tmp_path), 2, query_buckets=(8,),
                      poll_interval=0.01, supervise=True,
                      health_check_s=0.01, max_restarts=0)
    try:
        rset.replicas[0].kill()
        deadline = time.monotonic() + 5.0
        while rset.quarantined < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)  # many sweeps later...
        assert rset.quarantined == 1  # ...still counted exactly once
        assert rset.restarts == 0
        assert len(rset.healthy_replicas) == 1
    finally:
        rset.stop()
        svc.close()


def test_fire_kills_is_gen_scheduled_and_once_only(tmp_path):
    svc, rset = _replicated(tmp_path)
    plan = FaultPlan(kills=(ReplicaKill(replica_id=1, at_gen=3),))
    assert fire_kills(plan, rset, writer_gen=2) == []  # too early
    assert rset.replicas[1].healthy
    fired = fire_kills(plan, rset, writer_gen=3)
    assert fired == [plan.kills[0]]
    assert not rset.replicas[1].healthy
    assert fire_kills(plan, rset, writer_gen=9) == []  # once only
    svc.close()


# ------------------------------------------------- tailer vs trim race ---


def _fill_segments(svc, rng, n=6):
    for _ in range(n):
        svc._apply_ops(*chunk(rng))


def test_tailer_poll_raises_typed_wal_trimmed(tmp_path):
    svc = make_writer(tmp_path, segment_bytes=64)  # rotate every chunk
    rng = np.random.default_rng(4)
    tailer = oplog.LogTailer(wal_dir(str(tmp_path)), from_gen=0)
    _fill_segments(svc, rng)
    assert tailer.poll(2)  # cursor sits in an early segment
    svc.snapshot_now()  # trims every segment the snapshot covers
    with pytest.raises(fault_errors.WalTrimmed):
        while True:
            tailer.poll()
            break  # pragma: no cover -- poll must raise first
    svc.close()


def test_replica_absorbs_trim_as_resync_not_exception(tmp_path):
    svc = make_writer(tmp_path, segment_bytes=64)
    rng = np.random.default_rng(5)
    rep = Replica(str(tmp_path), query_buckets=(8,), auto_tail=False)
    _fill_segments(svc, rng)
    assert rep.tail_once(2) == 2  # cursor parked in an early segment
    svc.snapshot_now()
    before = rep.resyncs
    applied = rep.tail_once()  # trimmed underneath: resync, no raise
    assert rep.resyncs == before + 1 and applied == 0
    while rep.tail_once() or rep.gen < svc.gen:
        pass
    assert rep.gen == svc.gen
    assert leaves_equal(rep.service.state, svc.state)
    svc.close()


def test_tailer_constructor_survives_trim_race(tmp_path, monkeypatch):
    svc = make_writer(tmp_path, segment_bytes=64)
    rng = np.random.default_rng(6)
    _fill_segments(svc, rng)
    # the race: a segment is listed, then trimmed before its header is
    # read -- the constructor must re-list, not leak FileNotFoundError
    real = oplog.segment_base_gen
    calls = {"n": 0}

    def flaky(path):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise FileNotFoundError(path)
        return real(path)

    monkeypatch.setattr(oplog, "segment_base_gen", flaky)
    tailer = oplog.LogTailer(wal_dir(str(tmp_path)), from_gen=svc.gen)
    assert calls["n"] > 2  # retried through the race
    assert tailer.poll() == []

    # and when the segments never stop vanishing, the typed signal
    # (WalTrimmed) surfaces instead of an infinite loop
    calls["n"] = -10_000
    with pytest.raises(fault_errors.WalTrimmed):
        oplog.LogTailer(wal_dir(str(tmp_path)), from_gen=svc.gen)
    svc.close()


def test_tailer_empty_directory_still_file_not_found(tmp_path):
    os.makedirs(str(tmp_path / "w"), exist_ok=True)
    with pytest.raises(FileNotFoundError):
        oplog.LogTailer(str(tmp_path / "w"))


# ------------------------------------------------------------ stalls -----


def test_stall_injection_delays_broker_flush():
    cfg = tiny_cfg()
    svc = SCCService(cfg, state=gs.all_singletons(cfg), **KNOBS)
    broker = QueryBroker(svc, buckets=(8,))
    plan = FaultPlan(stalls=(Stall("broker_flush", first=0, count=1,
                                   seconds=0.05),))
    with injected(plan):
        t0 = time.monotonic()
        fut = broker.submit("same_scc", [0], [1])
        snap = broker.resolve(fut)
        assert time.monotonic() - t0 >= 0.045
    assert snap.gen == svc.gen


# --------------------------------------------------------- chaos smoke ---


@pytest.mark.slow
def test_chaos_soak_tiny(tmp_path):
    from repro.launch.chaos import run_chaos_soak

    rep = run_chaos_soak(str(tmp_path), seed=0, profile="mixed",
                         n_chunks=12, chunk=8, nv=48, replicas=2,
                         poll_interval=0.01, n_queries=4)
    assert rep["violations"] == []
    assert rep["acked"] + len(rep["failed"]) == rep["chunks"]


def test_client_end_to_end_over_degraded_replicated_store(tmp_path):
    """Integration: writer + replicas + typed client riding a WAL fault
    window -- acked writes visible through AT_LEAST reads afterwards."""
    svc = make_writer(tmp_path)
    rset = ReplicaSet(str(tmp_path), 2, query_buckets=(8,),
                      auto_tail=False)
    wclient = GraphClient(svc, max_retries=16, backoff_base_s=0.001,
                          backoff_cap_s=0.01)
    plan = FaultPlan(fs=(FsFault("write", "wal", first=1, count=2),))
    with injected(plan):
        for i in range(6):
            wclient.submit_many([AddEdge(i, (i + 1) % NV)])
    assert plan.triggered and svc.health == HEALTHY
    for r in rset.replicas:
        while r.tail_once():
            pass
    rclient = GraphClient(svc, broker=rset)
    res = rclient.submit_many(
        [SameSCC(0, 1)], consistency=Consistency.AT_LEAST(svc.gen))
    assert res[0].gen >= svc.gen
    svc.close()
