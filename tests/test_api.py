"""The typed client API: GraphClient, op vocabulary, consistency levels.

Contracts pinned here (see ``docs/SERVICE_API.md``):

* **differential**: a mixed typed-op stream (all four update kinds, all
  query kinds including the broker-path community queries) driven through
  one READ_YOUR_WRITES client session matches the sequential python
  oracle op for op -- updates under the documented per-bucket phase
  linearization, every query at exactly the submission-point state;
* **stamps**: generation stamps returned to a single client are monotone
  in submission order and (property test) never below the session's
  read-your-writes token at submission;
* **consistency levels**: LATEST never blocks, AT_LEAST blocks until a
  covering commit exists (and is answered at ``gen >= floor``),
  READ_YOUR_WRITES floors reads at the last acked update;
* the op encoders are the only typed<->raw bridge and reject misuse.
"""
import collections
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.api import (AddEdge, AddVertex, AtLeast, CommunityOf,
                       CommunitySizes, Consistency, GraphClient, Reachable,
                       RemoveEdge, RemoveVertex, SameSCC, SccMembers,
                       UpdateOp, encode_updates, updates_from_arrays)
from repro.core import dynamic, graph_state as gs
from repro.core.broker import QueryBroker
from repro.core.service import SCCService
from oracle import SeqSCC

NV = 20
PHASE = {dynamic.REM_VERTEX: 0, dynamic.REM_EDGE: 1,
         dynamic.ADD_VERTEX: 2, dynamic.ADD_EDGE: 3}


def tiny_cfg(edge_capacity=64, max_probes=8, nv=NV):
    return gs.GraphConfig(n_vertices=nv, edge_capacity=edge_capacity,
                          max_probes=max_probes, max_outer=nv + 1,
                          max_inner=nv + 2)


def make_client(consistency=Consistency.LATEST, **svc_kw):
    svc = SCCService(tiny_cfg(), buckets=svc_kw.pop("buckets", (8, 16)),
                     **svc_kw)
    return GraphClient(svc, consistency=consistency)


def booted(client: GraphClient, oracle: SeqSCC | None = None):
    res = client.submit_many([AddVertex(i) for i in range(NV)])
    assert all(r.value for r in res)
    if oracle is not None:
        for i in range(NV):
            assert oracle.add_vertex(i)


def oracle_apply(oracle: SeqSCC, op: UpdateOp) -> bool:
    if isinstance(op, AddEdge):
        return oracle.add_edge(op.u, op.v)
    if isinstance(op, RemoveEdge):
        return oracle.remove_edge(op.u, op.v)
    if isinstance(op, AddVertex):
        return oracle.add_vertex(op.u)
    return oracle.remove_vertex(op.u)


def oracle_replay_run(oracle: SeqSCC, sched, run):
    """Oracle results for one update run under the client's per-bucket
    phase linearization (the contract test_service pins for raw chunks)."""
    want = [False] * len(run)
    for sl, _ in sched.plan(len(run)):
        order = sorted(range(sl.start, sl.stop),
                       key=lambda i: (PHASE[run[i].KIND], i))
        for i in order:
            want[i] = oracle_apply(oracle, run[i])
    return want


def oracle_reachable(oracle: SeqSCC, u, v) -> bool:
    if not (0 <= u < oracle.n and 0 <= v < oracle.n):
        return False
    if not (oracle.alive[u] and oracle.alive[v]):
        return False
    adj = collections.defaultdict(list)
    for a, b in oracle.edges:
        adj[a].append(b)
    seen, frontier = {u}, [u]
    while frontier:
        nxt = []
        for x in frontier:
            for y in adj[x]:
                if y not in seen:
                    seen.add(y)
                    nxt.append(y)
        frontier = nxt
    return v in seen


def oracle_query(oracle: SeqSCC, op) -> object:
    cc = oracle.ccid()

    def lab(x):
        return cc[x] if 0 <= x < oracle.n else oracle.n

    if isinstance(op, SameSCC):
        return lab(op.u) < oracle.n and lab(op.u) == lab(op.v)
    if isinstance(op, Reachable):
        return oracle_reachable(oracle, op.u, op.v)
    if isinstance(op, SccMembers):
        return [lab(op.u) < oracle.n and cc[w] == lab(op.u)
                for w in range(oracle.n)]
    if isinstance(op, CommunityOf):
        return lab(op.u)
    # CommunitySizes
    hist = [0] * oracle.n
    for w in range(oracle.n):
        if cc[w] < oracle.n:
            hist[cc[w]] += 1
    return hist


def mixed_typed_stream(rng, n):
    """Random mix of every op kind (updates biased to keep a live graph)."""
    out = []
    for _ in range(n):
        roll = rng.random()
        a = int(rng.integers(0, NV))
        b = int(rng.integers(0, NV))
        if roll < 0.35:
            out.append(AddEdge(a, b))
        elif roll < 0.45:
            out.append(RemoveEdge(a, b))
        elif roll < 0.50:
            out.append(AddVertex(a))
        elif roll < 0.55:
            out.append(RemoveVertex(a))
        elif roll < 0.75:
            out.append(SameSCC(a, b))
        elif roll < 0.85:
            out.append(Reachable(a, b))
        elif roll < 0.90:
            out.append(SccMembers(a))
        elif roll < 0.97:
            out.append(CommunityOf(a))
        else:
            out.append(CommunitySizes())
    return out


# ------------------------------------------------------- differential -----


def test_mixed_typed_stream_differential_vs_oracle():
    """The acceptance contract: every result of a mixed typed stream
    (READ_YOUR_WRITES session) equals the sequential oracle at the op's
    submission point; stamps are monotone and cover the session token."""
    client = make_client(consistency=Consistency.READ_YOUR_WRITES)
    oracle = SeqSCC(NV)
    booted(client, oracle)
    sched = client.service._sched
    rng = np.random.default_rng(42)
    last_gen = -1
    for step in range(10):
        ops = mixed_typed_stream(rng, int(rng.integers(4, 28)))
        token_before = client.token
        results = client.submit_many(ops)
        assert len(results) == len(ops)
        # walk results in submission order, replaying update runs through
        # the oracle at run boundaries (the client's own batching rule)
        i = 0
        while i < len(results):
            r = results[i]
            if isinstance(r.op, UpdateOp):
                j = i
                while j < len(results) and isinstance(results[j].op,
                                                      UpdateOp):
                    j += 1
                run = [results[k].op for k in range(i, j)]
                want = oracle_replay_run(oracle, sched, run)
                got = [results[k].value for k in range(i, j)]
                assert got == want, f"update run mismatch at step {step}"
                i = j
                continue
            want = oracle_query(oracle, r.op)
            got = r.value.tolist() if isinstance(r.value, np.ndarray) \
                else r.value
            assert got == want, f"{r.op} mismatch at step {step}"
            # READ_YOUR_WRITES: stamped at or after the session token
            assert r.gen >= token_before
            i += 1
        # stamps monotone in submission order; token tracks acked updates
        gens = [r.gen for r in results]
        assert gens == sorted(gens)
        assert last_gen <= gens[0]
        last_gen = gens[-1]
        assert client.token == client.service.gen
    # final state agrees wholesale
    assert np.asarray(client.service.state.ccid).tolist() == oracle.ccid()
    assert client.service.edge_set() == oracle.edges
    client.close()


# ------------------------------------------------------ property test -----


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 9), st.integers(0, NV - 1),
                          st.integers(0, NV - 1)),
                min_size=1, max_size=40))
def test_gen_stamps_monotone_and_cover_ryw_token(raw):
    """Property: generation stamps returned to a single client session are
    monotone non-decreasing in submission order, and under
    READ_YOUR_WRITES no query is ever answered below the session token at
    its submission."""
    def to_op(code, a, b):
        return [AddEdge(a, b), AddEdge(a, b), RemoveEdge(a, b),
                AddVertex(a), RemoveVertex(a), SameSCC(a, b),
                Reachable(a, b), SccMembers(a), CommunityOf(a),
                CommunitySizes()][code]

    client = make_client(consistency=Consistency.READ_YOUR_WRITES)
    booted(client)
    stamps = []
    for code, a, b in raw:
        token = client.token
        res = client.submit(to_op(code, a, b)).result()
        stamps.append(res.gen)
        if not isinstance(res.op, UpdateOp):
            assert res.gen >= token, (res, token)
        else:
            assert client.token >= token
    assert stamps == sorted(stamps), stamps
    assert client.token <= client.service.gen
    client.close()


# -------------------------------------------------- consistency levels ----


def test_at_least_blocks_until_covering_commit():
    """AT_LEAST(g) with g beyond the committed line defers (gen-wait hook,
    visible in telemetry) and resolves only once a covering commit lands;
    AT_LEAST at or below the line never blocks."""
    svc = SCCService(tiny_cfg(), buckets=(8,))
    broker = QueryBroker(svc, buckets=(4,)).start()
    try:
        writer = GraphClient(svc, broker=broker)
        reader = GraphClient(svc, broker=broker)
        booted(writer)
        writer.submit_many([AddEdge(0, 1), AddEdge(1, 0)])
        g = svc.gen
        # at-or-below the committed line: answered promptly
        res = reader.submit(SameSCC(0, 1),
                            consistency=Consistency.AT_LEAST(g)).result(
                                timeout=5)
        assert res.value is True and res.gen >= g
        # beyond the line: must wait for the covering commit
        fut = reader.submit(SameSCC(0, 2),
                            consistency=Consistency.AT_LEAST(g + 1))
        time.sleep(0.15)
        assert not fut.done(), "AT_LEAST answered below its floor"
        writer.submit_many([AddEdge(1, 2), AddEdge(2, 0)])
        res = fut.result(timeout=5)
        assert res.gen >= g + 1
        assert res.value is True  # 0,1,2 now one SCC at the stamped gen
        assert broker.stats()["gen_waits"] > 0
    finally:
        broker.stop()


def test_at_least_inline_with_concurrent_writer():
    """Inline mode (no dispatcher): an AT_LEAST read parks on the
    service's commit condition until another session's write covers it."""
    svc = SCCService(tiny_cfg(), buckets=(8,))
    client = GraphClient(svc)
    booted(client)
    g = svc.gen

    def late_writer():
        time.sleep(0.15)
        w = GraphClient(svc)
        w.submit_many([AddEdge(0, 1)])
        w.close()

    t = threading.Thread(target=late_writer)
    t.start()
    res = client.submit(SameSCC(0, 1),
                        consistency=AtLeast(g + 1)).result(timeout=10)
    t.join()
    assert res.gen >= g + 1
    client.close()


def test_read_your_writes_token_advances_with_acks():
    client = make_client(consistency=Consistency.READ_YOUR_WRITES)
    booted(client)
    t0 = client.token
    assert t0 == client.service.gen  # seeded at the committed line
    res = client.submit_many([AddEdge(0, 1)])
    assert client.token == res[0].gen > t0
    q = client.submit(SameSCC(0, 1)).result()
    assert q.gen >= client.token
    client.close()


def test_stopped_broker_fails_uncoverable_floor():
    """stop() must not hang on a floor no commit will ever cover: the
    deferred request is failed instead."""
    svc = SCCService(tiny_cfg(), buckets=(8,))
    broker = QueryBroker(svc, buckets=(4,)).start()
    client = GraphClient(svc, broker=broker)
    booted(client)
    fut = client.submit(SameSCC(0, 1),
                        consistency=Consistency.AT_LEAST(svc.gen + 100))
    time.sleep(0.1)
    broker.stop()  # must return, not deadlock
    with pytest.raises(RuntimeError):
        fut.result(timeout=5)


# --------------------------------------------------- community queries ----


def test_community_queries_through_broker():
    """CommunityOf/CommunitySizes are broker kinds: coalesced, stamped,
    sentinel-correct -- and consistent with the (min-id) label contract."""
    svc = SCCService(tiny_cfg(), buckets=(8,))
    client = GraphClient(svc)
    booted(client)
    client.submit_many([AddEdge(0, 1), AddEdge(1, 2), AddEdge(2, 0),
                        AddEdge(3, 4), AddEdge(4, 3), RemoveVertex(5)])
    res = client.submit_many([CommunityOf(0), CommunityOf(1),
                              CommunityOf(3), CommunityOf(5),
                              CommunityOf(NV + 3), CommunitySizes()])
    labs, hist = [r.value for r in res[:-1]], res[-1].value
    assert labs[0] == labs[1] == 0       # min-id canonical label
    assert labs[2] == 3
    assert labs[3] == NV                 # dead vertex: sentinel
    assert labs[4] == NV                 # out-of-range: sentinel, no alias
    assert hist[0] == 3 and hist[3] == 2 and hist[5] == 0
    assert int(hist.sum()) == NV - 1     # one vertex removed
    assert len({r.gen for r in res}) == 1 == len({res[0].gen, svc.gen})
    # broker wrappers agree with the client path
    snap = client.broker.community_of([0, 5])
    assert snap.value.tolist() == [0, NV]
    assert client.broker.community_sizes().value.tolist() == hist.tolist()
    client.close()


# ----------------------------------------------------- vocabulary/misc ----


def test_encoders_roundtrip_and_reject_misuse():
    ops = [AddEdge(1, 2), RemoveEdge(2, 3), AddVertex(4), RemoveVertex(5)]
    kind, u, v = encode_updates(ops)
    assert kind.tolist() == [dynamic.ADD_EDGE, dynamic.REM_EDGE,
                             dynamic.ADD_VERTEX, dynamic.REM_VERTEX]
    assert u.tolist() == [1, 2, 4, 5]
    assert v.tolist() == [2, 3, 0, 0]
    assert updates_from_arrays(kind, u, v) == ops
    # NOP lanes (scheduler padding) decode away
    assert updates_from_arrays([dynamic.NOP], [0], [0]) == []
    with pytest.raises(TypeError):
        encode_updates([AddEdge(0, 1), SameSCC(0, 1)])
    client = make_client()
    with pytest.raises(TypeError):
        client.submit("add_edge")
    with pytest.raises(TypeError):
        client.submit_many([AddEdge(0, 1), "same_scc"])
    with pytest.raises(TypeError):  # unknown consistency level
        client.submit_many([SameSCC(0, 1)], consistency="latest")
    client.close()


def test_ops_are_frozen_values():
    op = AddEdge(1, 2)
    with pytest.raises(Exception):
        op.u = 9
    assert op == AddEdge(1, 2) and op != AddEdge(2, 1)
    assert SameSCC(1, 2) != Reachable(1, 2)


def test_client_stats_unify_service_and_broker():
    client = make_client()
    booted(client)
    client.submit_many([AddEdge(0, 1), SameSCC(0, 1)])
    s = client.stats()
    for key in ("gen", "pipelined_chunks", "fallback_chunks",
                "compile_count", "grows", "flushes", "served",
                "gen_waits", "coalescing", "client_updates",
                "client_queries", "ryw_token"):
        assert key in s, key
    assert s["client_updates"] == NV + 1
    assert s["client_queries"] == 1
    client.close()


def test_sessions_share_service_updates_serialize():
    """Two client sessions over one service: interleaved typed updates
    serialize on the service's update lock; both observe a single commit
    line (and the final state matches one sequential history)."""
    svc = SCCService(tiny_cfg(), buckets=(8,))
    a = GraphClient(svc)
    b = GraphClient(svc)
    booted(a)
    errors = []

    def worker(client, seed):
        try:
            rng = np.random.default_rng(seed)
            for _ in range(6):
                u, v = int(rng.integers(0, NV)), int(rng.integers(0, NV))
                client.submit_many([AddEdge(u, v), SameSCC(u, v)])
        except Exception as e:
            errors.append(e)

    ts = [threading.Thread(target=worker, args=(c, s))
          for c, s in ((a, 1), (b, 2))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors[0]
    assert a.gen == b.gen == svc.gen
    # commit line is one total order: both sessions' tokens are covered
    assert max(a.token, b.token) <= svc.gen
    a.close()
    b.close()


def test_gen_continuity_across_checkpoint_restore(tmp_path):
    """The serving example's recovery contract in miniature: a checkpoint
    round-trips the generation counter, and a client session over the
    restored service resumes exactly at the recorded committed gen."""
    from repro.ckpt import checkpoint

    client = make_client()
    booted(client)
    client.submit_many([AddEdge(0, 1), AddEdge(1, 0), RemoveVertex(7)])
    svc = client.service
    saved_gen = svc.gen
    checkpoint.save(str(tmp_path), 1,
                    {"state": svc.state, "gen": np.int64(saved_gen)})
    tpl = {"state": gs.empty(svc.cfg), "gen": np.int64(0)}
    restored, _ = checkpoint.restore(str(tmp_path), tpl)
    svc2 = SCCService(svc.cfg, buckets=(8, 16), state=restored["state"])
    client2 = GraphClient(svc2, consistency=Consistency.READ_YOUR_WRITES)
    assert int(restored["gen"]) == saved_gen
    assert client2.gen == saved_gen == client2.token
    # and the restored session answers at (or after) the restored line
    res = client2.submit(SameSCC(0, 1)).result()
    assert res.value is True and res.gen >= saved_gen
    client.close()
    client2.close()
