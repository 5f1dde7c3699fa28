"""Write-path high-availability suite (PR-10).

Pins the lease/epoch/fencing contract that makes writer failover safe:

  * :class:`repro.ha.lease.FileLease`: atomic fresh acquire, mutual
    exclusion while the holder heartbeats, monotone epoch bump on
    takeover, graceful release vs SIGKILL-style abandon;
  * the epoch-fenced WAL (:mod:`repro.ckpt.oplog`): v2 segment headers
    round-trip the writer epoch, legacy ``SCCWAL01`` segments read (and
    replay) as epoch 0, a fence marker makes every stale-epoch append
    raise :class:`~repro.fault.errors.Fenced` with NOTHING written, and
    the tail-repair utilities truncate a mixed-epoch log to the newest
    epoch's clean prefix;
  * :class:`~repro.ckpt.durable.DurableService` leadership: a writer
    whose lease was taken over self-fences with a typed
    :class:`~repro.fault.errors.NotLeader`; :meth:`Replica.promote`
    drains the fenced tail and produces a bit-identical next-epoch
    leader (differential oracle);
  * ``GraphClient`` failover behavior: ``NotLeader`` reroutes the
    session to ``leader_resolver()`` and resubmits; retry backoff uses
    seeded decorrelated jitter (deterministic under an injected RNG,
    never a lockstep geometric ladder);
  * multi-tenant lanes: an injected WAL fault on one tenant is a typed
    retryable reject chained to the cause, counted in that tenant's
    telemetry, and invisible to other tenants -- with the lane's store
    still bit-identical to its acked-op oracle afterwards.
"""
import os
import tempfile
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.api import AddEdge, GraphClient
from repro.api.ops import encode_updates
from repro.ckpt import oplog
from repro.ckpt.durable import FENCED, DurableService, wal_dir
from repro.core import graph_state as gs
from repro.core.replicas import Replica, ReplicaSet
from repro.core.service import SCCService
from repro.fault import errors as fault_errors
from repro.ha.lease import FileLease

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


def listing(directory):
    return sorted((f, os.path.getsize(os.path.join(directory, f)))
                  for f in os.listdir(directory))


def acquire_stale(lease, timeout_s=5.0):
    """Poll try_acquire until the current holder's lease goes stale."""
    deadline = time.monotonic() + timeout_s
    while not lease.try_acquire():
        assert time.monotonic() < deadline, "lease never went stale"
        time.sleep(lease.ttl_s / 5)


# ---------------------------------------------------------------- lease ---


def test_lease_fresh_acquire_is_exclusive_and_takeover_bumps_epoch(
        tmp_path):
    a = FileLease(str(tmp_path), "a", ttl_s=0.15)
    b = FileLease(str(tmp_path), "b", ttl_s=0.15)
    assert a.try_acquire() and a.epoch == 0 and a.valid
    assert not b.try_acquire()  # holder is alive (mtime fresh)
    a.renew()
    assert a.renewals == 1
    time.sleep(0.2)  # a stops renewing: the lease goes stale
    acquire_stale(b)
    assert b.epoch == 1 and b.takeovers == 1
    # the deposed holder's next renewal is a typed loss, flipping valid
    with pytest.raises(fault_errors.LeaseLost):
        a.renew()
    assert not a.valid and a.lost_reason is not None
    info = b.peek()
    assert (info.epoch, info.owner) == (1, "b")


def test_lease_release_hands_off_without_a_ttl_wait(tmp_path):
    a = FileLease(str(tmp_path), "a", ttl_s=30.0)  # huge TTL
    assert a.try_acquire()
    a.release()  # backdates mtime: successor need not wait 30s
    b = FileLease(str(tmp_path), "b", ttl_s=30.0)
    assert b.try_acquire() and b.epoch == 1


def test_lease_heartbeat_keeps_holder_alive_and_abandon_models_sigkill(
        tmp_path):
    a = FileLease(str(tmp_path), "a", ttl_s=0.15)
    assert a.try_acquire()
    a.start_heartbeat()
    time.sleep(0.5)  # several TTLs: the heartbeat must keep it fresh
    b = FileLease(str(tmp_path), "b", ttl_s=0.15)
    assert not b.try_acquire() and a.valid and a.renewals >= 2
    a.abandon()  # SIGKILL analogue: no backdate, heartbeat stops dead
    assert not b.try_acquire()  # still fresh: failover waits the TTL
    acquire_stale(b)
    assert b.epoch == 1


# ----------------------------------------------------- epoch-fenced WAL ---


@settings(max_examples=12)
@given(st.integers(0, 2 ** 31 - 1), st.integers(0, 2 ** 20))
def test_segment_header_roundtrips_epoch_and_base_gen(epoch, base_gen):
    with tempfile.TemporaryDirectory(prefix="scc-hdr-") as d:
        w = oplog.OpLogWriter(d, sync_every=1, start_gen=base_gen,
                              epoch=epoch)
        w.close()
        _, path = oplog.list_segments(d)[-1]
        hdr = oplog.segment_header(path)
        assert (hdr.base_gen, hdr.epoch) == (base_gen, epoch)
        assert hdr.size == oplog.SEG_HEADER_BYTES
        assert oplog.newest_epoch(d) == epoch


def test_fence_refuses_stale_appends_with_nothing_written(tmp_path):
    d = str(tmp_path)
    rng = np.random.default_rng(0)
    w = oplog.OpLogWriter(d, sync_every=1, start_gen=0)
    k, u, v = chunk(rng)
    w.append(0, k, u, v)
    oplog.write_fence(d, 1)
    before = listing(d)
    with pytest.raises(fault_errors.Fenced):
        w.append(1, *chunk(rng))
    assert listing(d) == before, "a fenced append left bytes behind"
    w.close()
    assert listing(d) == before
    # a resurrected writer at the dead epoch is refused before it can
    # even create a segment
    with pytest.raises(fault_errors.Fenced):
        oplog.OpLogWriter(d, sync_every=1, start_gen=2, epoch=0)
    assert listing(d) == before
    # everything appended before the fence stays durable and readable
    assert [r.gen_before for r in oplog.read_log(d)] == [0]
    # the next epoch appends freely
    w1 = oplog.OpLogWriter(d, sync_every=1, start_gen=1, epoch=1)
    w1.append(1, *chunk(rng))
    w1.close()
    assert [r.gen_before for r in oplog.read_log(d)] == [0, 1]


@settings(max_examples=8)
@given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 48))
def test_mixed_epoch_tail_truncates_to_newest_epochs_clean_prefix(
        n_a, n_b, torn_words):
    """repair_tail / drop_unapplied_tail on a WAL whose tail spans a
    failover: epoch-0 segments, a fence, then epoch-1 segments ending in
    torn bytes.  Repair must drop exactly the junk; the unapplied-record
    cut must land inside the newest epoch; replay yields every surviving
    record across both epochs in order."""
    rng = np.random.default_rng(n_a * 101 + n_b)
    with tempfile.TemporaryDirectory(prefix="scc-mixed-") as d:
        gen = 0
        w0 = oplog.OpLogWriter(d, sync_every=1, start_gen=0)
        for _ in range(n_a):
            w0.append(gen, *chunk(rng))
            gen += 1
        w0.close()
        oplog.write_fence(d, 1)
        w1 = oplog.OpLogWriter(d, sync_every=1, start_gen=gen, epoch=1)
        for _ in range(n_b):
            w1.append(gen, *chunk(rng))
            gen += 1
        w1.close()
        segs = oplog.list_segments(d)
        assert oplog.segment_header(segs[0][1]).epoch == 0
        assert oplog.segment_header(segs[-1][1]).epoch == 1
        with open(segs[-1][1], "ab") as f:  # crash-torn tail
            f.write(b"\xde\xad" * torn_words)
        assert oplog.repair_tail(d) == 2 * torn_words
        recs = oplog.read_log(d)
        assert [r.gen_before for r in recs] == list(range(gen))
        assert oplog.newest_epoch(d) == 1
        # a valid-but-unacked record at the newest epoch's tail is cut
        # without touching the older epoch's segments
        assert oplog.drop_unapplied_tail(d, gen - 1) > 0
        recs = oplog.read_log(d)
        assert [r.gen_before for r in recs] == list(range(gen - 1))
        assert oplog.segment_header(segs[0][1]).epoch == 0


def test_v1_segments_read_and_replay_as_epoch_zero(tmp_path):
    """Back-compat: a pre-epoch store (SCCWAL01 headers) must recover
    bit-identically, reading every segment as epoch 0."""
    writer = make_writer(tmp_path)
    rng = np.random.default_rng(3)
    chunks = [chunk(rng) for _ in range(4)]
    for c in chunks:
        writer._apply_ops(*c)
    writer.close()
    wdir = wal_dir(str(tmp_path))
    for _, path in oplog.list_segments(wdir):  # rewrite headers as v1
        with open(path, "rb") as f:
            buf = f.read()
        hdr = oplog.parse_segment_header(buf, path)
        assert hdr.epoch == 0 and hdr.size == oplog.SEG_HEADER_BYTES
        with open(path, "wb") as f:
            f.write(oplog._SEG_HDR_V1.pack(oplog._SEG_MAGIC_V1,
                                           hdr.base_gen))
            f.write(buf[hdr.size:])
        v1 = oplog.segment_header(path)
        assert (v1.base_gen, v1.epoch, v1.size) == (hdr.base_gen, 0,
                                                    oplog._SEG_HDR_V1.size)
    assert oplog.newest_epoch(wdir) == 0
    reopened = DurableService.open(str(tmp_path), snapshot_every=0)
    cfg = tiny_cfg()
    oracle = SCCService(cfg, state=gs.all_singletons(cfg), **KNOBS)
    for c in chunks:
        oracle._apply_ops(*c)
    assert reopened.gen == oracle.gen
    assert leaves_equal(reopened.state, oracle.state)
    reopened.close()


# ------------------------------------------------- writer-side fencing ---


def test_writer_self_fences_when_its_lease_is_taken_over(tmp_path):
    lease_a = FileLease(str(tmp_path), "a", ttl_s=0.15)
    assert lease_a.try_acquire()
    writer = make_writer(tmp_path, lease=lease_a)
    rng = np.random.default_rng(5)
    writer._apply_ops(*chunk(rng))
    writer.crash()  # heartbeat stops dead, lease left behind
    lease_b = FileLease(str(tmp_path), "b", ttl_s=0.15)
    acquire_stale(lease_b)
    assert lease_b.epoch == 1
    with pytest.raises(fault_errors.NotLeader) as ei:
        writer._apply_ops(*chunk(rng))
    assert ei.value.retryable
    assert writer.health == FENCED
    assert writer.stats()["notleader_rejects"] >= 1
    writer.close()


def test_promotion_is_a_bit_identical_next_epoch_handoff(tmp_path):
    """Differential oracle across a promotion: old-leader chunks + new-
    leader chunks replayed through a plain in-memory service must equal
    the promoted leader AND a cold reopen -- and the dead writer stays
    typed-rejected."""
    cfg = tiny_cfg()
    lease_a = FileLease(str(tmp_path), "a", ttl_s=0.15)
    assert lease_a.try_acquire()
    writer = make_writer(tmp_path, lease=lease_a)
    rng = np.random.default_rng(11)
    chunks = [chunk(rng) for _ in range(5)]
    for c in chunks:
        writer._apply_ops(*c)
    writer.crash()
    rep = Replica(str(tmp_path), 0, query_buckets=(8,), auto_tail=False)
    lease_b = FileLease(str(tmp_path), "b", ttl_s=0.15)
    deadline = time.monotonic() + 5.0
    leader = None
    while leader is None:
        try:
            leader = rep.promote(lease_b, snapshot_every=0)
        except fault_errors.Unavailable:
            assert time.monotonic() < deadline, "promotion never won"
            time.sleep(0.03)
    try:
        assert leader.epoch == 1 and leader.gen == writer.gen
        more = [chunk(rng) for _ in range(3)]
        for c in more:
            leader._apply_ops(*c)
        # the deposed writer keeps bouncing typed errors, applies nothing
        with pytest.raises(fault_errors.NotLeader):
            writer._apply_ops(*chunk(rng))
        oracle = SCCService(cfg, state=gs.all_singletons(cfg), **KNOBS)
        for c in chunks + more:
            oracle._apply_ops(*c)
        assert leader.gen == oracle.gen
        assert leaves_equal(leader.state, oracle.state)
    finally:
        leader.close()
        rep.stop()
        writer.close()
    reopened = DurableService.open(str(tmp_path), snapshot_every=0)
    assert reopened.epoch >= 1  # cold recovery adopts the fenced epoch
    assert reopened.gen == oracle.gen
    assert leaves_equal(reopened.state, oracle.state)
    reopened.close()


def test_replicaset_supervisor_promotes_on_stale_writer_lease(tmp_path):
    lease = FileLease(str(tmp_path), "writer", ttl_s=0.15)
    assert lease.try_acquire()
    writer = make_writer(tmp_path, lease=lease)
    rng = np.random.default_rng(17)
    for _ in range(3):
        writer._apply_ops(*chunk(rng))
    rset = ReplicaSet(str(tmp_path), 2, query_buckets=(8,),
                      poll_interval=0.02, supervise=True,
                      health_check_s=0.03, promote_on_writer_loss=True,
                      lease_ttl_s=0.15,
                      writer_kwargs=dict(sync_every=1, snapshot_every=0))
    try:
        assert rset.leader is None  # healthy writer: nothing to promote
        time.sleep(0.4)
        assert rset.leader is None and rset.promotions == 0
        writer.crash()
        deadline = time.monotonic() + 8.0
        while rset.leader is None:
            assert time.monotonic() < deadline, (
                f"supervisor never promoted "
                f"(last={rset.last_promote_error})")
            time.sleep(0.02)
        leader = rset.leader
        assert rset.promotions == 1 and leader.epoch == 1
        leader._apply_ops(*chunk(rng))  # the new leader accepts writes
        assert leader.gen == writer.gen + 1
    finally:
        rset.stop()  # also closes the promoted leader
        writer.close()


# --------------------------------------------------- client failover ----


class _DeposedService:
    """Stub of a writer that lost leadership: every chunk bounces."""

    def __init__(self):
        self.gen = 0
        self.attempts = 0

    def _apply_ops(self, kind, u, v, *, session=None, seq=None):
        self.attempts += 1
        raise fault_errors.NotLeader("leadership moved", leader="peer",
                                     retry_after=0.001)

    def stats(self):
        return {}


class _LeaderService:
    """Stub of the current leader: applies everything."""

    def __init__(self):
        self.gen = 0
        self.applied = 0

    def _apply_ops(self, kind, u, v, *, session=None, seq=None):
        self.gen += 1
        self.applied += 1
        return np.ones(len(kind), bool), self.gen

    def stats(self):
        return {}


def test_client_reroutes_on_notleader_and_resubmits():
    import random
    old, new = _DeposedService(), _LeaderService()
    client = GraphClient(old, max_retries=4, backoff_base_s=1e-4,
                         backoff_cap_s=1e-3, rng=random.Random(0),
                         leader_resolver=lambda: new)
    res = client.submit_many([AddEdge(0, 1)])
    assert res[0].gen == 1 and new.applied == 1
    assert old.attempts == 1  # one bounce, then the session moved
    assert client.stats()["client_reroutes"] == 1
    client.submit_many([AddEdge(1, 2)])  # subsequent ops go straight
    assert old.attempts == 1 and new.applied == 2


def test_client_without_resolver_surfaces_notleader_after_retries():
    old = _DeposedService()
    client = GraphClient(old, max_retries=3, backoff_base_s=1e-4,
                         backoff_cap_s=1e-3)
    with pytest.raises(fault_errors.NotLeader):
        client.submit_many([AddEdge(0, 1)])
    assert old.attempts == 4  # initial + max_retries


class _Flaky:
    def __init__(self, n_fail):
        self.gen = 0
        self.n_fail = n_fail
        self.attempts = 0

    def _apply_ops(self, kind, u, v, *, session=None, seq=None):
        self.attempts += 1
        if self.attempts <= self.n_fail:
            raise fault_errors.Unavailable("transient",
                                           retry_after=0.0001)
        self.gen += 1
        return np.ones(len(kind), bool), self.gen


def test_retry_backoff_jitter_is_seeded_and_decorrelated(monkeypatch):
    import random

    def run(seed):
        waits = []
        monkeypatch.setattr(time, "sleep",
                            lambda s, rec=waits: rec.append(s))
        try:
            client = GraphClient(_Flaky(6), max_retries=8,
                                 backoff_base_s=0.004,
                                 backoff_cap_s=0.5,
                                 rng=random.Random(seed))
            client.submit_many([AddEdge(0, 1)])
        finally:
            monkeypatch.undo()
        return waits

    a, b, c = run(7), run(7), run(11)
    assert len(a) == 6
    assert a == b, "same RNG seed must reproduce the wait schedule"
    assert a != c, "different seeds must decorrelate the schedule"
    assert len(set(a)) > 1, "jitter collapsed to a fixed ladder"
    assert all(0.004 <= w <= 0.5 for w in a)


# ------------------------------------------------------- tenant lanes ----


def test_tenant_wal_fault_is_typed_isolated_and_counted(tmp_path):
    from repro.tenancy import MultiTenantService

    cfg = tiny_cfg()
    knobs = dict(buckets=(8,), scan_lengths=(1,))
    mts = MultiTenantService(cfg, directory=str(tmp_path),
                             tenant_batches=(1, 2), coalesce_ops=16,
                             flush_deadline_s=0.0, wal_sync_every=1,
                             **knobs)
    ta, tb = mts.create_tenant(), mts.create_tenant()
    ca = mts.client(ta, max_retries=0)
    cb = mts.client(tb, max_retries=0)
    ca.submit_many([AddEdge(0, 1)])
    cb.submit_many([AddEdge(1, 2)])
    h = mts._tenants[ta]
    real_append = h.wal.append
    state = {"failed": False}

    def sick_append(*args, **kw):
        if not state["failed"]:
            state["failed"] = True
            raise OSError(5, "injected tenant-lane disk fault")
        return real_append(*args, **kw)

    h.wal.append = sick_append
    with pytest.raises(fault_errors.Unavailable) as ei:
        ca.submit_many([AddEdge(2, 3)])
    assert ei.value.retryable and ei.value.retry_after is not None
    assert isinstance(ei.value.__cause__, OSError)
    # the fault is A's alone: B's lane flushes normally, telemetry
    # blames exactly one lane
    cb.submit_many([AddEdge(3, 4)])
    assert mts.tenant_stats(ta)["wal_faults"] == 1
    assert mts.tenant_stats(tb)["wal_faults"] == 0
    # the failed chunk was neither applied nor acked: a resubmit lands
    # exactly once and the lane stays oracle-identical, disk included
    ca.submit_many([AddEdge(2, 3)])
    oracle = SCCService(cfg, **knobs)
    for op in ([AddEdge(0, 1)], [AddEdge(2, 3)]):
        oracle._apply_ops(*encode_updates(op))
    assert mts.tenant_gen(ta) == oracle.gen == 2
    assert leaves_equal(mts._tenant_state(ta), oracle.state)
    mts.close()
    cold = DurableService.open(os.path.join(str(tmp_path), "tenants",
                                            ta), snapshot_every=0)
    assert cold.gen == oracle.gen
    assert leaves_equal(cold.state, oracle.state)
    cold.close()
