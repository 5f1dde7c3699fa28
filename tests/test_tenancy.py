"""Multi-tenant engine + service: differential vs single-tenant oracles.

The tenancy subsystem's contract is that stacking tenants behind one
vmapped engine is an *execution strategy*, not a semantics change: every
tenant's acks, generation trajectory, labelling, and live edge set must
be bit-identical to a lone :class:`repro.core.service.SCCService` fed
the same chunks -- including when another tenant forces the overflow
grow-and-replay fallback, and across an evict/rehydrate round trip
through the PR-6 durable store.  The admission queue's backpressure and
flush-trigger behaviour is pinned separately at the queue layer.
"""
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import dynamic, graph_state as gs
from repro.core.service import SCCService
from repro.tenancy import (MultiTenantService, QueueFull, TenantEngine,
                           TransferBufferPool, WorkQueue)

NV = 24


def tiny_cfg(edge_capacity=64, nv=NV):
    return gs.GraphConfig(n_vertices=nv, edge_capacity=edge_capacity,
                          max_probes=8, max_outer=nv + 1,
                          max_inner=nv + 2)


ENGINE_KNOBS = dict(buckets=(8, 16), scan_lengths=(1, 4))
ORACLE_KNOBS = dict(buckets=(8, 16), scan_lengths=(1, 4))


def oracle_for(cfg):
    return SCCService(cfg, **ORACLE_KNOBS)


def rand_chunk(rng, n, nv=NV):
    """Mixed update chunk: mostly edge churn, some vertex churn."""
    kind = rng.choice(
        [dynamic.ADD_EDGE, dynamic.ADD_EDGE, dynamic.ADD_EDGE,
         dynamic.REM_EDGE, dynamic.ADD_VERTEX, dynamic.ADD_VERTEX,
         dynamic.REM_VERTEX], size=n).astype(np.int32)
    u = rng.integers(0, nv, n).astype(np.int32)
    v = rng.integers(0, nv, n).astype(np.int32)
    return kind, u, v


def assert_tenant_matches(engine_state, engine_cfg, engine_gen, oracle,
                          ctx=""):
    assert engine_gen == int(oracle.gen), ctx
    assert engine_cfg == oracle.cfg, ctx
    assert np.array_equal(np.asarray(engine_state.ccid),
                          np.asarray(oracle.state.ccid)), ctx
    got_edges = SCCService(engine_cfg, state=engine_state).edge_set()
    assert got_edges == oracle.edge_set(), ctx


# --------------------------------------------------------------- engine


def test_engine_differential_vs_oracles():
    """3 tenants, 14 interleaved waves of random mixed chunks (varying
    sizes -> different buckets, shape-grouped dispatches, tenant-batch
    padding, idle tenants): acks, gens, labels, and edge sets must match
    three independent single-tenant services bit-for-bit."""
    cfg = tiny_cfg()
    eng = TenantEngine(tenant_batches=(1, 2, 3), **ENGINE_KNOBS)
    tids = ["a", "b", "c"]
    for tid in tids:
        eng.create_tenant(tid, cfg)
    oracles = {tid: oracle_for(cfg) for tid in tids}
    rng = np.random.default_rng(7)
    for round_i in range(14):
        wave, want = [], {}
        for tid in tids:
            if round_i and rng.random() < 0.25:
                continue            # idle tenant: must not be stepped
            n = int(rng.integers(1, 25))
            kind, u, v = rand_chunk(rng, n)
            wave.append((tid, kind, u, v))
            want[tid] = oracles[tid]._apply_ops(kind, u, v)
        res = eng.apply_chunks(wave)
        for tid, (want_ok, want_gen) in want.items():
            got_ok, got_gen = res[tid]
            assert np.array_equal(got_ok, np.asarray(want_ok)), \
                (round_i, tid)
            assert got_gen == want_gen, (round_i, tid)
        for tid in tids:
            assert eng.tenant_gen(tid) == int(oracles[tid].gen), \
                (round_i, tid)
    for tid in tids:
        assert_tenant_matches(eng.tenant_state(tid), eng.tenant_cfg(tid),
                              eng.tenant_gen(tid), oracles[tid], tid)
    assert eng.compile_count <= eng.compile_bound


def test_engine_overflow_isolation():
    """Tenant 'hog' overflows its tiny table and takes the solo
    grow-and-replay fallback; the victims sharing its dispatches must
    commit from the same wave untouched (zero fallbacks) and everyone
    stays bit-identical to their oracle."""
    cfg = tiny_cfg(edge_capacity=8)
    eng = TenantEngine(tenant_batches=(1, 2, 3), **ENGINE_KNOBS)
    tids = ["hog", "v1", "v2"]
    for tid in tids:
        eng.create_tenant(tid, cfg)
    oracles = {tid: oracle_for(cfg) for tid in tids}
    rng = np.random.default_rng(11)
    boot = np.arange(NV, dtype=np.int32)
    for tid in tids:
        kind = np.full(NV, dynamic.ADD_VERTEX, np.int32)
        want = oracles[tid]._apply_ops(kind, boot, boot)
        got = eng.apply_chunks([(tid, kind, boot, boot)])[tid]
        assert np.array_equal(got[0], np.asarray(want[0]))
    for round_i in range(6):
        wave, want = [], {}
        # hog: dense distinct-edge adds, guaranteed past capacity 8
        ku = rng.integers(0, NV, 16).astype(np.int32)
        kv = rng.integers(0, NV, 16).astype(np.int32)
        kind = np.full(16, dynamic.ADD_EDGE, np.int32)
        wave.append(("hog", kind, ku, kv))
        want["hog"] = oracles["hog"]._apply_ops(kind, ku, kv)
        for tid in ("v1", "v2"):
            k, u, v = rand_chunk(rng, 4)
            k[:] = np.where(k == dynamic.ADD_EDGE, dynamic.NOP, k)
            wave.append((tid, k, u, v))
            want[tid] = oracles[tid]._apply_ops(k, u, v)
        res = eng.apply_chunks(wave)
        for tid in tids:
            got_ok, got_gen = res[tid]
            assert np.array_equal(got_ok, np.asarray(want[tid][0])), \
                (round_i, tid)
            assert got_gen == want[tid][1], (round_i, tid)
    hog = eng.tenant_telemetry("hog")
    assert hog["fallback_chunks"] > 0, "hog never overflowed"
    assert hog["grows"] > 0
    assert eng.tenant_cfg("hog").edge_capacity > 8
    for tid in ("v1", "v2"):
        tel = eng.tenant_telemetry(tid)
        assert tel["fallback_chunks"] == 0, f"{tid} was dragged off " \
            "the fast path by another tenant's overflow"
    for tid in tids:
        assert_tenant_matches(eng.tenant_state(tid), eng.tenant_cfg(tid),
                              eng.tenant_gen(tid), oracles[tid], tid)


def test_engine_compile_bound():
    """The compiled-entry registry stays under the asserted
    ``tenant_batches x scan_lengths x buckets x cfgs`` ceiling no matter
    how chunks arrive, and idle-shape entries are never minted."""
    cfg = tiny_cfg()
    eng = TenantEngine(buckets=(8,), scan_lengths=(1,),
                       tenant_batches=(1, 2))
    for tid in ("a", "b", "c"):
        eng.create_tenant(tid, cfg)
    rng = np.random.default_rng(3)
    for _ in range(4):
        wave = [(tid, *rand_chunk(rng, 8)) for tid in ("a", "b", "c")]
        eng.apply_chunks(wave)
    # 3 tenants split as tb=2 + tb=1 over one bucket/scan/cfg
    assert eng.compile_count == 2
    assert eng.compile_count <= eng.compile_bound == 2


def test_pin_reads_committed_view_while_lock_is_held():
    """A pin never takes the engine lock: with another thread holding it
    and a wave queued behind it, a pin returns the last committed
    (state, gen) at once; after the wave, the next pin sees the wave."""
    cfg = tiny_cfg()
    eng = TenantEngine(tenant_batches=(1, 2), **ENGINE_KNOBS)
    oracle = oracle_for(cfg)
    eng.create_tenant("a", cfg)
    eng.create_tenant("b", cfg)
    rng = np.random.default_rng(13)
    boot = (np.full(NV, dynamic.ADD_VERTEX, np.int32),
            np.arange(NV, dtype=np.int32), np.arange(NV, dtype=np.int32))
    eng.apply_chunks([("a", *boot)])
    oracle._apply_ops(*boot)
    committed = (np.asarray(oracle.state.ccid), int(oracle.gen))
    held, release = threading.Event(), threading.Event()

    def hold():
        with eng._lock:
            held.set()
            release.wait(30)

    chunk = rand_chunk(rng, 12)
    holder = threading.Thread(target=hold)
    writer = threading.Thread(
        target=lambda: eng.apply_chunks([("a", *chunk)]))
    got = {}
    reader = threading.Thread(target=lambda: got.update(
        pin=eng.pin("a"), gen=eng.tenant_gen("a"),
        cfg=eng.tenant_cfg("a")))
    holder.start()
    assert held.wait(10)
    writer.start()
    reader.start()
    reader.join(5)
    try:
        assert not reader.is_alive(), "a pin waited for the engine lock"
    finally:
        release.set()
        holder.join(10)
        writer.join(60)
    state, pcfg, gen = got["pin"]
    assert (gen, got["gen"]) == (committed[1], committed[1])
    assert pcfg == got["cfg"] == cfg and isinstance(gen, int)
    assert np.array_equal(np.asarray(state.ccid), committed[0])
    oracle._apply_ops(*chunk)
    state, pcfg, gen = eng.pin("a")
    assert gen == int(oracle.gen) > committed[1]
    assert np.array_equal(np.asarray(state.ccid),
                          np.asarray(oracle.state.ccid))
    assert eng.stats()["pins"] == 2


def test_concurrent_reads_match_oracle_at_their_generation():
    """Reader threads over typed clients race writer threads over a few
    tiny tenants: every answer equals the oracle replay of that tenant
    at the generation the answer carries."""
    from repro.api import CommunityOf, GraphClient, SameSCC
    from repro.core.service import community_of_on, same_scc_on

    cfg = tiny_cfg()
    mts = MultiTenantService(cfg, tenant_batches=(1, 2, 3),
                             coalesce_ops=64, flush_deadline_s=0.001,
                             **ENGINE_KNOBS)
    tids = [mts.create_tenant() for _ in range(3)]
    acks = {tid: [] for tid in tids}         # (chunk, ok, gen) in order
    answers = []                             # (tid, op, value, gen)
    errors, flushes = [], []
    go, done = threading.Event(), threading.Event()
    first_pass = threading.Barrier(3, action=go.set)

    def write(i, tid):
        rng = np.random.default_rng(100 + i)
        sess = mts.session(tid)
        go.wait(60)                 # every reader has read gen 0
        try:
            ids = np.arange(NV, dtype=np.int32)
            chunk = (np.full(NV, dynamic.ADD_VERTEX, np.int32), ids, ids)
            for _ in range(7):
                ok, gen = sess._apply_ops(*chunk)
                acks[tid].append((chunk, np.asarray(ok), gen))
                chunk = rand_chunk(rng, int(rng.integers(3, 9)))
        except Exception as e:               # surfaced below
            errors.append(e)

    def read(i):
        rng = np.random.default_rng(200 + i)
        clients = {tid: GraphClient(mts.session(tid)) for tid in tids}

        def one_pass():
            for tid in tids:
                pts = rng.integers(0, NV, (4, 2))
                ops = [SameSCC(int(a), int(b)) for a, b in pts] + \
                      [CommunityOf(int(a)) for a, _ in pts]
                for op, r in zip(ops, clients[tid].submit_many(ops)):
                    answers.append((tid, op, r.value, r.gen))

        try:
            one_pass()
            first_pass.wait(60)
            while not done.wait(0.002):
                one_pass()
            one_pass()              # and every final generation
        except Exception as e:
            errors.append(e)
        finally:
            flushes.append(sum(c.broker.flushes for c in clients.values()))
            for c in clients.values():
                c.close()

    writers = [threading.Thread(target=write, args=(i, tid))
               for i, tid in enumerate(tids)]
    readers = [threading.Thread(target=read, args=(i,)) for i in range(3)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-3)
    try:
        for th in readers + writers:
            th.start()
        for th in writers:
            th.join(120)
        done.set()
        for th in readers:
            th.join(60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(th.is_alive() for th in writers + readers)
    mts.close()
    assert not errors, errors
    # one pin per broker flush, none lost to a racing increment
    assert mts.engine.stats()["pins"] == sum(flushes)
    for tid in tids:
        oracle = oracle_for(cfg)
        at = {0: (oracle.state, oracle.cfg)}
        for chunk, ok, gen in acks[tid]:
            want_ok, want_gen = oracle._apply_ops(*chunk)
            assert np.array_equal(ok, np.asarray(want_ok)) and \
                gen == want_gen, tid
            at[gen] = (oracle.state, oracle.cfg)
        mine = [a for a in answers if a[0] == tid]
        gens = {a[3] for a in mine}
        assert gens <= set(at), f"{tid}: answers at unknown gens"
        assert len(gens) > 1, f"{tid}: reads saw no commit"
        ids = np.arange(NV, dtype=np.int32)
        want = {}                           # gen -> (same [NV, NV], comm)
        for gen in gens:
            st, scfg = at[gen]
            same = same_scc_on(st, scfg, np.repeat(ids, NV), np.tile(ids, NV))
            want[gen] = (same.reshape(NV, NV), community_of_on(st, scfg, ids))
        for _, op, value, gen in mine:
            if isinstance(op, SameSCC):
                assert value == want[gen][0][op.u, op.v], (tid, op, gen)
            else:
                assert value == want[gen][1][op.u], (tid, op, gen)


def test_repeated_pins_add_no_jit_entry():
    """The lane slice is one jitted program per stack shape: after the
    first pin of a group, pins of any lane, across commits, compile
    nothing; a new capacity group adds at most one entry."""
    from repro.tenancy import engine as engine_mod

    cfg = tiny_cfg()
    eng = TenantEngine(tenant_batches=(1, 2, 4), **ENGINE_KNOBS)
    tids = ("a", "b", "c")
    for tid in tids:
        eng.create_tenant(tid, cfg)
    rng = np.random.default_rng(17)

    def wave():
        eng.apply_chunks([(tid, *rand_chunk(rng, 6)) for tid in tids])

    wave()                          # the wave's own gathers compile here
    eng.pin("a")
    entries = engine_mod._take._cache_size()
    for _ in range(3):
        wave()
        for tid in tids:
            eng.pin(tid)
    assert engine_mod._take._cache_size() == entries
    eng.create_tenant("big", tiny_cfg(edge_capacity=128))
    eng.pin("big")
    grown = engine_mod._take._cache_size()
    assert grown <= entries + 1
    for _ in range(3):
        eng.pin("big")
        eng.pin("b")
    assert engine_mod._take._cache_size() == grown
    assert eng.stats()["pins"] == 1 + 9 + 1 + 6


# -------------------------------------------------------------- service


def test_service_clients_differential():
    """Typed per-tenant GraphClient sessions over the admission queue:
    update acks and RYW generations match per-tenant oracles."""
    from repro.api import AddEdge, AddVertex, SameSCC

    cfg = tiny_cfg()
    mts = MultiTenantService(cfg, tenant_batches=(1, 2), coalesce_ops=64,
                             flush_deadline_s=0.0, **ENGINE_KNOBS)
    t0, t1 = mts.create_tenant(), mts.create_tenant()
    oracles = {t0: oracle_for(cfg), t1: oracle_for(cfg)}
    clients = {tid: mts.client(tid) for tid in (t0, t1)}
    rng = np.random.default_rng(5)
    for tid in (t0, t1):
        ops = [AddVertex(i) for i in range(NV)]
        res = clients[tid].submit_many(ops)
        kind = np.full(NV, dynamic.ADD_VERTEX, np.int32)
        ids = np.arange(NV, dtype=np.int32)
        want_ok, want_gen = oracles[tid]._apply_ops(kind, ids, ids)
        assert [r.value for r in res] == np.asarray(want_ok).tolist()
        assert all(r.gen == want_gen for r in res)
    for _ in range(5):
        for tid in (t0, t1):
            pairs = rng.integers(0, NV, (6, 2)).astype(np.int32)
            ops = [AddEdge(int(a), int(b)) for a, b in pairs]
            res = clients[tid].submit_many(ops)
            kind = np.full(6, dynamic.ADD_EDGE, np.int32)
            want_ok, want_gen = oracles[tid]._apply_ops(
                kind, pairs[:, 0], pairs[:, 1])
            assert [r.value for r in res] == np.asarray(want_ok).tolist()
            assert all(r.gen == want_gen for r in res)
    # queries answer from the committed per-tenant lane
    for tid in (t0, t1):
        qs = [SameSCC(int(a), int(b)) for a, b in
              rng.integers(0, NV, (8, 2))]
        got = [r.value for r in clients[tid].submit_many(qs)]
        from repro.core.service import same_scc_on
        want = same_scc_on(oracles[tid].state, oracles[tid].cfg,
                           [q.u for q in qs], [q.v for q in qs])
        assert got == np.asarray(want).tolist()
        assert mts.tenant_gen(tid) == int(oracles[tid].gen)
    for tid in (t0, t1):
        clients[tid].close()
    mts.close()


def test_service_evict_rehydrate_roundtrip(tmp_path):
    """Evict parks the tenant on disk (lane released, stats preserved);
    the next touch rebuilds it from snapshot + WAL tail bit-identically,
    and post-rehydration writes keep matching the oracle."""
    cfg = tiny_cfg()
    mts = MultiTenantService(cfg, tenant_batches=(1, 2),
                             directory=str(tmp_path), coalesce_ops=64,
                             flush_deadline_s=0.0, **ENGINE_KNOBS)
    tid = mts.create_tenant()
    other = mts.create_tenant()
    oracle = oracle_for(cfg)
    sess = mts.session(tid)
    rng = np.random.default_rng(9)
    boot = np.arange(NV, dtype=np.int32)
    kind = np.full(NV, dynamic.ADD_VERTEX, np.int32)
    sess._apply_ops(kind, boot, boot)
    oracle._apply_ops(kind, boot, boot)
    for _ in range(4):
        k, u, v = rand_chunk(rng, 12)
        got = sess._apply_ops(k, u, v)
        want = oracle._apply_ops(k, u, v)
        assert np.array_equal(got[0], np.asarray(want[0]))
        assert got[1] == want[1]
    pre_gen = mts.tenant_gen(tid)
    pre_ccid = np.asarray(sess.state.ccid)

    mts.evict(tid)
    st = mts.tenant_stats(tid)
    assert st["resident"] is False and st["evictions"] == 1
    assert st["gen"] == pre_gen          # parked stats stay queryable
    assert mts.tenant_gen(tid) == pre_gen
    occ = mts.engine.occupancy()
    assert occ["tenants"] == 1, "evicted lane was not released"
    assert other in mts.engine.tenant_ids()

    # touch: state read rehydrates bit-identically
    assert np.array_equal(np.asarray(sess.state.ccid), pre_ccid)
    assert mts.tenant_stats(tid)["rehydrations"] == 1
    assert mts.tenant_gen(tid) == pre_gen
    # and the rehydrated tenant keeps tracking the oracle
    for _ in range(3):
        k, u, v = rand_chunk(rng, 10)
        got = sess._apply_ops(k, u, v)
        want = oracle._apply_ops(k, u, v)
        assert np.array_equal(got[0], np.asarray(want[0]))
        assert got[1] == want[1]
    assert_tenant_matches(sess.state, sess.cfg, mts.tenant_gen(tid),
                          oracle, "post-rehydration")
    mts.close()


# ---------------------------------------------------------------- queue


def test_queue_backpressure_and_flush_triggers():
    """Over-budget submits are rejected immediately with a retry hint
    (never block-and-grow); an under-budget lone submit flushes by
    deadline; a size-triggered wave coalesces multiple tenants."""
    gate = threading.Event()
    waves = []

    def apply_fn(reqs):
        gate.wait(10)
        waves.append(sorted(t for t, *_ in reqs))
        return {t: (np.ones(k.shape[0], bool), 1) for t, k, u, v in reqs}

    q = WorkQueue(apply_fn, max_pending_ops=8, coalesce_ops=64,
                  flush_deadline_s=0.01)
    z4 = np.zeros(4, np.int32)
    leader = threading.Thread(target=lambda: q.submit("a", z4, z4, z4))
    leader.start()
    time.sleep(0.1)          # leader hit its deadline, is inside apply_fn
    follower = threading.Thread(target=lambda: q.submit(
        "b", np.zeros(8, np.int32), np.zeros(8, np.int32),
        np.zeros(8, np.int32)))
    follower.start()
    time.sleep(0.05)         # follower admitted: budget now full
    with pytest.raises(QueueFull) as ei:
        q.submit("c", z4, z4, z4)
    assert ei.value.retry_after > 0
    assert q.stats()["rejects"] == 1
    gate.set()
    leader.join(5)
    follower.join(5)
    assert not leader.is_alive() and not follower.is_alive()
    assert q.stats()["flush_causes"]["deadline"] >= 1
    assert ["a"] in waves and ["b"] in waves

    # size trigger: two tenants' chunks coalesce into one wave
    q2 = WorkQueue(apply_fn, max_pending_ops=64, coalesce_ops=8,
                   flush_deadline_s=5.0)
    gate.clear()
    waves.clear()
    ts = [threading.Thread(target=lambda t=t: q2.submit(t, z4, z4, z4))
          for t in ("x", "y")]
    for t in ts:
        t.start()
    time.sleep(0.1)
    gate.set()
    for t in ts:
        t.join(5)
        assert not t.is_alive()
    assert q2.stats()["flush_causes"]["size"] >= 1
    assert ["x", "y"] in waves, f"no coalesced wave in {waves}"


def test_transfer_pool_reuse():
    """Steady-state submits recycle pooled buffers (no allocation)."""
    pool = TransferBufferPool(buckets=(8, 32), per_bucket=2)
    a = pool.acquire(5)
    assert a.cap == 8
    pool.release(a)
    b = pool.acquire(7)
    assert b is a, "freelist buffer was not reused"
    big = pool.acquire(100)          # oversize: one-off exact alloc
    assert big.cap == 100
    pool.release(big)                # not pooled
    assert pool.acquire(100) is not big
    s = pool.stats()
    assert s["hits"] == 1 and s["misses"] >= 2
