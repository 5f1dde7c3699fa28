"""Read-replica suite (PR-6): WAL-tailing replicas behind the broker.

Pins the replication-layer contracts of ``repro.core.replicas``:

  * a :class:`Replica` bootstraps from the writer's snapshot and, after
    tailing the WAL, is **bit-identical** to the writer at every
    committed generation it passes through -- same state leaves, same
    ``same_scc`` / ``community_of`` answers;
  * ``AT_LEAST(gen)`` on a stale replica *defers*: the broker serves
    nothing for that request until the replica has tailed past ``gen``
    (``gen_waits`` telemetry), while floor-free requests on the same
    replica are never delayed behind it;
  * :class:`ReplicaSet` routing: requests whose floor some replica
    already covers go to a fresh replica (``routed_fresh``); requests
    nobody covers yet are parked on one replica (``routed_stale``) and
    served once it tails -- and a served stamp is never below the floor,
    so per-reader generation stamps stay monotone even when consecutive
    reads land on *different* replicas (the session-floor contract);
  * writer, tailing replica, and the sequential python oracle
    (``tests/oracle.py``) agree op-for-op on random mixed streams --
    per-op acks, labels, edge sets, generations;
  * a replica whose WAL cursor is trimmed underneath it (writer
    snapshotted + dropped old segments) resyncs from the newest
    snapshot and converges anyway;
  * the typed :class:`repro.api.GraphClient` runs writes through the
    writer and READ_YOUR_WRITES reads through a :class:`ReplicaSet`.

Everything here drives replicas manually (``auto_tail=False``) so the
tests are single-threaded and deterministic; the threaded tail/dispatch
path is exercised by the crash smoke and the replica bench
(``python -m repro.launch.replica``).
"""
import numpy as np
import pytest

from repro.api import (AddEdge, Consistency, GraphClient, RemoveEdge,
                       SameSCC)
from repro.ckpt.durable import DurableService
from repro.core import dynamic, graph_state as gs
from repro.core import service as svc_mod
from repro.core.replicas import Replica, ReplicaSet
from oracle import SeqSCC

NV = 24
KNOBS = dict(buckets=(8,), proactive_grow=True)
PHASE = {dynamic.REM_VERTEX: 0, dynamic.REM_EDGE: 1,
         dynamic.ADD_VERTEX: 2, dynamic.ADD_EDGE: 3}
QU = np.arange(8, dtype=np.int32) % NV
QV = (QU * 5 + 3) % NV


def tiny_cfg():
    return gs.GraphConfig(n_vertices=NV, edge_capacity=64, max_probes=16,
                          max_outer=NV + 1, max_inner=NV + 2)


def make_writer(directory, **durable_kw):
    cfg = tiny_cfg()
    durable_kw.setdefault("snapshot_every", 0)  # boot snapshot only
    return DurableService(cfg, str(directory), state=gs.all_singletons(cfg),
                          sync_every=1, **durable_kw, **KNOBS)


def random_chunk(rng, n=8):
    return (rng.integers(0, 4, n).astype(np.int32),
            rng.integers(0, NV, n).astype(np.int32),
            rng.integers(0, NV, n).astype(np.int32))


def drain(replica):
    while True:  # a resync applies nothing itself but re-seats the cursor
        before = replica.resyncs
        if replica.tail_once() == 0 and replica.resyncs == before:
            return


def assert_same_graph(a_state, a_cfg, b_state, b_cfg, ctx=""):
    import jax
    got = jax.tree_util.tree_leaves(a_state)
    want = jax.tree_util.tree_leaves(b_state)
    assert len(got) == len(want), ctx
    for x, y in zip(got, want):
        assert np.array_equal(np.asarray(x), np.asarray(y)), ctx
    assert np.array_equal(svc_mod.same_scc_on(a_state, a_cfg, QU, QV),
                          svc_mod.same_scc_on(b_state, b_cfg, QU, QV)), ctx
    assert np.array_equal(svc_mod.community_of_on(a_state, a_cfg, QU),
                          svc_mod.community_of_on(b_state, b_cfg, QU)), ctx


def oracle_chunk(oracle, kind, u, v):
    """Per-op oracle acks for ONE service chunk (ops phase-sorted within
    the chunk, like the engine's removal/insert phases)."""
    want = np.zeros(len(kind), bool)
    order = sorted(range(len(kind)),
                   key=lambda i: (PHASE[int(kind[i])], i))
    for i in order:
        k, uu, vv = int(kind[i]), int(u[i]), int(v[i])
        if k == dynamic.ADD_EDGE:
            want[i] = oracle.add_edge(uu, vv)
        elif k == dynamic.REM_EDGE:
            want[i] = oracle.remove_edge(uu, vv)
        elif k == dynamic.ADD_VERTEX:
            want[i] = oracle.add_vertex(uu)
        else:
            want[i] = oracle.remove_vertex(uu)
    return want


# --------------------------------------------------------- bootstrap ------


def test_replica_bootstraps_and_tails_bit_identical(tmp_path):
    """Boot-snapshot bootstrap + full tail == the writer, bit for bit;
    the replica's broker stamps answers with the replica generation."""
    writer = make_writer(tmp_path)
    rng = np.random.default_rng(7)
    for _ in range(6):
        writer._apply_ops(*random_chunk(rng))

    rep = Replica(str(tmp_path), auto_tail=False, query_buckets=(8,))
    assert rep.gen == 0, "bootstraps from the generation-0 boot snapshot"
    drain(rep)
    assert rep.gen == writer.gen
    assert rep.applied_records == 6
    assert_same_graph(rep.service.state, rep.service.cfg,
                      writer.state, writer.cfg, "after full tail")
    assert rep.service.edge_set() == writer.edge_set()

    snap = rep.broker.same_scc(QU, QV)  # inline flush, no dispatcher
    assert snap.gen == rep.gen
    assert np.array_equal(
        np.asarray(snap.value),
        svc_mod.same_scc_on(writer.state, writer.cfg, QU, QV))
    writer.close()


# ---------------------------------------------------------- gen-wait ------


def test_at_least_defers_on_stale_replica_until_tailed(tmp_path):
    """AT_LEAST(G) on a replica still below G is re-queued (gen_waits)
    and served only after the replica tails past G -- floor-free
    requests on the same replica are answered immediately meanwhile."""
    writer = make_writer(tmp_path)
    rng = np.random.default_rng(8)
    for _ in range(4):
        writer._apply_ops(*random_chunk(rng))
    goal = writer.gen

    rep = Replica(str(tmp_path), auto_tail=False, query_buckets=(8,))
    assert rep.tail_once(max_records=2) == 2
    stale_gen = rep.gen
    assert 0 < stale_gen < goal

    fut = rep.broker.submit("same_scc", QU, QV, min_gen=goal)
    assert rep.broker.flush() == 0, "stale replica must not answer"
    assert not fut.done()
    assert rep.broker.gen_waits == 1

    # a floor-free reader is not delayed behind the deferred request
    free = rep.broker.submit("same_scc", QU, QV)
    assert rep.broker.flush() == len(QU)
    assert free.result().gen == stale_gen
    assert not fut.done()
    assert rep.broker.gen_waits == 1, "deferral is counted once"

    drain(rep)
    assert rep.broker.flush() == len(QU)
    snap = fut.result()
    assert snap.gen >= goal
    assert np.array_equal(
        np.asarray(snap.value),
        svc_mod.same_scc_on(writer.state, writer.cfg, QU, QV))
    writer.close()


# ------------------------------------------------------------ routing -----


def test_replicaset_routes_fresh_and_parks_stale(tmp_path):
    """Floors some replica covers route fresh (never to a replica below
    the floor); uncovered floors park on one replica and serve once it
    tails -- stamps never dip below a session's floor even when reads
    hop replicas."""
    writer = make_writer(tmp_path)
    rng = np.random.default_rng(9)
    for _ in range(4):
        writer._apply_ops(*random_chunk(rng))
    g4 = writer.gen

    rs = ReplicaSet(str(tmp_path), 2, auto_tail=False, query_buckets=(8,))
    r0, r1 = rs.replicas
    drain(r0)                       # r0 at g4, r1 still at 0
    assert rs.min_gen == 0

    fut = rs.submit("same_scc", QU, QV, min_gen=g4)
    snap = rs.resolve(fut, min_gen=g4)
    assert rs.routed_fresh == 1 and rs.routed_stale == 0
    assert snap.gen >= g4
    assert r1.broker.served == 0, "a stale replica never saw the floor"

    # advance the writer past every replica: nobody is fresh
    writer._apply_ops(*random_chunk(rng))
    g5 = writer.gen
    fut = rs.submit("same_scc", QU, QV, min_gen=g5)
    assert rs.routed_stale == 1
    # without tail threads the stale route falls back to the most
    # caught-up replica (etas are inf) -- that is r0
    assert r0.tail_once() > 0 and r0.gen == g5
    snap = rs.resolve(fut, min_gen=g5)
    assert snap.gen >= g5

    # session floor across replicas: a reader holding stamp g5 queries
    # again; only fresh replicas qualify, so the stamp stays monotone
    floor = int(snap.gen)
    fut = rs.submit("same_scc", QU, QV, min_gen=floor)
    snap2 = rs.resolve(fut, min_gen=floor)
    assert snap2.gen >= floor
    assert r1.gen < floor and r1.broker.served == 0

    drain(r1)
    assert rs.wait_all_for_gen(g5, timeout=1.0) == g5
    s = rs.stats()
    assert s["replicas"] == 2
    assert s["routed_fresh"] + s["routed_stale"] == 3
    assert s["replica0_gen"] == s["replica1_gen"] == g5
    writer.close()


# ------------------------------------------------- oracle differential ----


def test_writer_replica_oracle_differential(tmp_path):
    """Random mixed streams: writer acks == sequential oracle acks, and
    after each round the tailing replica matches both -- labels, edge
    set, generation; its broker stamps are monotone per reader."""
    writer = make_writer(tmp_path)
    oracle = SeqSCC(NV)
    for i in range(NV):
        assert oracle.add_vertex(i)  # all_singletons boots everything live

    rep = Replica(str(tmp_path), auto_tail=False, query_buckets=(8,))
    rng = np.random.default_rng(17)
    last_stamp = -1
    for round_no in range(10):
        kind, u, v = random_chunk(rng)
        ok, gen = writer._apply_ops(kind, u, v)
        want = oracle_chunk(oracle, kind, u, v)
        assert np.asarray(ok).tolist() == want.tolist(), \
            f"round {round_no}: writer acks diverge from oracle"

        drain(rep)
        assert rep.gen == writer.gen == gen
        assert np.asarray(rep.service.state.ccid).tolist() == \
            np.asarray(writer.state.ccid).tolist() == oracle.ccid()
        assert rep.service.edge_set() == writer.edge_set() == oracle.edges

        snap = rep.broker.same_scc(QU, QV)
        assert snap.gen >= last_stamp, "per-reader stamps must be monotone"
        last_stamp = int(snap.gen)
        lab = oracle.ccid()
        want_q = [lab[int(a)] == lab[int(b)] and lab[int(a)] < NV
                  for a, b in zip(QU, QV)]
        assert np.asarray(snap.value).tolist() == want_q
    writer.close()


# -------------------------------------------------------------- resync ----


def test_replica_resyncs_after_wal_trim(tmp_path):
    """A snapshot+trim that drops segments under a lagging replica's
    cursor forces a snapshot resync; the replica still converges to the
    writer's exact state."""
    writer = make_writer(tmp_path, segment_bytes=128,
                         trim_on_snapshot=True)
    rng = np.random.default_rng(23)
    writer._apply_ops(*random_chunk(rng))
    rep = Replica(str(tmp_path), auto_tail=False, query_buckets=(8,))
    assert rep.tail_once(max_records=1) == 1  # cursor parked early

    for _ in range(8):
        writer._apply_ops(*random_chunk(rng))
    writer.snapshot_now()  # trims the WAL below the snapshot gen
    writer._apply_ops(*random_chunk(rng))

    drain(rep)
    assert rep.resyncs >= 1, "trimmed cursor must trigger a resync"
    assert rep.gen == writer.gen
    assert_same_graph(rep.service.state, rep.service.cfg,
                      writer.state, writer.cfg, "post-resync")
    writer.close()


# ------------------------------------------------------- typed client -----


def test_graph_client_over_replicaset_read_your_writes(tmp_path):
    """The deployment shape from docs/SERVICE_API.md: GraphClient writes
    through the durable writer and reads from a ReplicaSet under
    READ_YOUR_WRITES -- every stamp covers the session's last ack."""
    writer = make_writer(tmp_path)
    rs = ReplicaSet(str(tmp_path), 2, auto_tail=False, query_buckets=(8,))
    client = GraphClient(writer, broker=rs,
                         consistency=Consistency.READ_YOUR_WRITES)

    ack = client.submit(AddEdge(1, 2)).result()
    assert ack.value and ack.gen == writer.gen
    ack2 = client.submit(AddEdge(2, 1)).result()
    assert client.token == ack2.gen

    for r in rs.replicas:
        drain(r)
    got = client.submit(SameSCC(1, 2)).result()
    assert got.value is True
    assert got.gen >= ack2.gen, "RYW floor must cover the last ack"

    # breaking the cycle flows through the same path
    client.submit(RemoveEdge(2, 1)).result()
    for r in rs.replicas:
        drain(r)
    got = client.submit(SameSCC(1, 2)).result()
    assert got.value is False
    assert got.gen >= client.token
    assert rs.stats()["routed_fresh"] == 2
    client.close()  # shared broker: the set is stopped explicitly
    rs.stop()
    writer.close()


def test_supervised_mode_refuses_an_accelerator_backend(tmp_path,
                                                        monkeypatch):
    """The multi-process drill spawns replica children that import JAX
    again; on a chip they would contend with the parent for it, so it
    refuses any backend but the CPU before writing or spawning."""
    import subprocess

    import jax
    from repro.launch import replica

    def no_spawn(*a, **k):
        raise AssertionError("a replica child was spawned")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    with pytest.raises(RuntimeError, match="CPU fault drill"):
        replica.supervised_stream(str(tmp_path), replicas=1, steps=1)
    assert not any(tmp_path.iterdir())
