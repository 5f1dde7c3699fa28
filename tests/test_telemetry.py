"""Spans and counters inside the service (``repro.telemetry``).

The tracing contract: spans nest by thread, a client request's id reaches
every layer it calls, the ring says when it dropped records, and spans
work with no profiler running.  The counters agree with the service's own
statistics: one ``repair.step`` event per resolved step, FW/BW and tier
round counts that are 0 on gated steps and positive where a deletion
splits an SCC, tenant lanes that report the tiers a lone service would,
and one ``queue.wait`` per admitted ticket inside its ``queue.submit``.
"""
import collections
import threading

import numpy as np

from repro import telemetry
from repro.api import AddEdge, GraphClient, SameSCC
from repro.core import dynamic, graph_state as gs
from repro.core.service import SCCService
from repro.tenancy import MultiTenantService, TenantEngine

NV = 24


def tiny_cfg(**kw):
    base = dict(n_vertices=NV, edge_capacity=64, max_probes=8,
                max_outer=NV + 1, max_inner=NV + 2)
    base.update(kw)
    return gs.GraphConfig(**base)


def since(rid: int, *names) -> list:
    """Records with an id above ``rid`` (made after it), by name."""
    recs, _ = telemetry.records()
    return [r for r in recs
            if r.id > rid and (not names or r.name in names)]


def mark() -> int:
    return telemetry.event("test.mark")


def rand_chunk(rng, n):
    kind = rng.choice([dynamic.ADD_EDGE] * 3 + [dynamic.REM_EDGE,
                                                dynamic.ADD_VERTEX,
                                                dynamic.REM_VERTEX],
                      size=n).astype(np.int32)
    return (kind, rng.integers(0, NV, n).astype(np.int32),
            rng.integers(0, NV, n).astype(np.int32))


def cycle_ops(n):
    """AddEdge i -> i+1 mod n: one SCC of n vertices."""
    return (np.full(n, dynamic.ADD_EDGE, np.int32),
            np.arange(n, dtype=np.int32),
            (np.arange(n, dtype=np.int32) + 1) % n)


def test_nested_spans_give_parents_without_profiler():
    m = mark()
    with telemetry.span("outer", a=1) as outer:
        with telemetry.span("inner") as inner:
            telemetry.event("leaf", n=3)
        inner.attrs["late"] = True
    got = {r.name: r for r in since(m, "outer", "inner", "leaf")}
    assert got["inner"].parent == outer.id
    assert got["leaf"].parent == inner.id
    assert got["outer"].parent == 0
    assert got["outer"].attrs == {"a": 1}
    assert got["inner"].attrs == {"late": True}
    assert got["leaf"].attrs == {"n": 3}
    assert got["outer"].t0_ns <= got["inner"].t0_ns <= got["leaf"].t0_ns \
        <= got["inner"].t1_ns <= got["outer"].t1_ns
    # the ring holds records in the order they ended
    assert [r.name for r in since(m, "outer", "inner", "leaf")] == \
        ["leaf", "inner", "outer"]


def test_spans_are_per_thread_and_requests_restore():
    m = mark()
    seen = {}

    def worker():
        with telemetry.request() as req:
            seen["req"] = req.id
            with telemetry.span("worker"):
                pass

    with telemetry.span("main"):
        th = threading.Thread(target=worker)
        th.start()
        th.join(10)
        assert not th.is_alive()
    assert telemetry.current_request() == 0
    got = {r.name: r for r in since(m, "main", "worker")}
    assert got["worker"].parent == 0          # another thread's stack
    assert got["worker"].req == seen["req"] and got["main"].req == 0


def test_ring_reports_what_it_dropped(monkeypatch):
    monkeypatch.setattr(telemetry, "_ring", collections.deque(maxlen=4))
    _, total0 = telemetry.records()
    ids = [telemetry.event("e", i=i) for i in range(6)]
    recs, total = telemetry.records()
    assert total - total0 == 6
    assert [r.id for r in recs] == ids[-4:]
    assert total - len(recs) >= 2             # the two oldest dropped


def test_request_id_reaches_broker_and_queue():
    mts = MultiTenantService(tiny_cfg(), buckets=(8, 16),
                             scan_lengths=(1, 4), tenant_batches=(1, 2),
                             flush_deadline_s=0.0)
    tid = mts.create_tenant()
    client = GraphClient(mts.session(tid))
    pins0 = mts.engine.stats()["pins"]
    m = mark()
    client.submit_many([AddEdge(0, 1), AddEdge(1, 0), SameSCC(0, 1)])
    client.close()
    mts.close()
    recs = since(m)
    by = collections.defaultdict(list)
    for r in recs:
        by[r.name].append(r)
    (upd,), (rd,) = by["client.update"], by["client.read"]
    assert upd.req and rd.req and upd.req != rd.req
    for name in ("queue.submit", "queue.wait", "queue.wave",
                 "engine.apply", "engine.dispatch", "engine.resolve",
                 "engine.commit"):
        assert [r.req for r in by[name]] == [upd.req], name
    for name in ("broker.flush", "broker.pin", "query.same_scc"):
        assert [r.req for r in by[name]] == [rd.req], name
    # a read pins the engine's published view and never takes its lock
    assert by["engine.lock_wait"] and \
        {r.req for r in by["engine.lock_wait"]} == {upd.req}
    (wave,) = by["engine.wave"]
    assert wave.attrs["lanes"] == 1 and wave.attrs["lane_steps"] == 1
    assert wave.attrs["tier_skipped"] + wave.attrs["tier_dense"] + \
        wave.attrs["tier_compact"] + wave.attrs["tier_full"] == 1
    (pin,) = by["broker.pin"]
    assert pin.parent == by["broker.flush"][0].id
    (engine_pin,) = by["engine.pin"]
    assert engine_pin.parent == pin.id and engine_pin.req == rd.req
    assert mts.engine.stats()["pins"] - pins0 == len(by["broker.pin"])


def test_service_pin_is_state_cfg_gen():
    """``SCCService.pin()`` gives the three values a broker flush used to
    read one by one, the generation as a host int."""
    svc = SCCService(tiny_cfg(), buckets=(8,), scan_lengths=(1,))
    for c in (cycle_ops(5), rand_chunk(np.random.default_rng(2), 7)):
        svc._apply_chunk(*c)
        st, cfg, gen = svc.pin()
        assert st is svc.state and cfg == svc.cfg
        assert type(gen) is int and gen == svc.gen == int(st.gen) > 0


def test_repair_step_events_match_stats_step_for_step():
    """Pipelined scan path and serial path: one event per step, equal
    event for event (round counts included), and summing to the tier
    counters of ``stats()``."""
    rng = np.random.default_rng(5)
    chunks = [rand_chunk(rng, n) for n in (5, 40, 17, 64, 3, 33)]
    events = {}
    for name, kw in (("scan", dict(inflight_window=2)),
                     ("serial", dict(inflight_window=0))):
        svc = SCCService(tiny_cfg(edge_capacity=256), buckets=(8, 16),
                         scan_lengths=(1, 4), **kw)
        m = mark()
        for c in chunks:
            svc._apply_chunk(*c)
        evs = since(m, "repair.step")
        events[name] = [tuple(sorted(e.attrs.items())) for e in evs]
        s = svc.stats()
        assert len(evs) == svc.gen
        for tier in dynamic.TIER_NAMES:
            assert sum(e.attrs["tier"] == tier for e in evs) == \
                s[f"repair_{tier}_steps"], (name, tier)
        assert sum(e.attrs["reach_rounds"] for e in evs) == \
            s["repair_reach_rounds"]
        assert sum(e.attrs["scc_rounds"] for e in evs) == \
            s["repair_scc_rounds"]
    assert events["scan"] == events["serial"]


def test_round_counts_zero_when_gated_positive_on_a_split():
    cfg = tiny_cfg(edge_capacity=256)
    svc = SCCService(cfg, buckets=(8,), scan_lengths=(1,),
                     state=gs.all_singletons(cfg))
    svc._apply_chunk(*cycle_ops(6))               # one SCC {0..5}
    assert int(svc.state.n_ccs) == NV - 5
    m = mark()
    # re-adding a live edge changes no structure: the gate skips repair
    svc._apply_chunk(np.int32([dynamic.ADD_EDGE]), np.int32([0]),
                     np.int32([1]))
    (gated,) = since(m, "repair.step")
    assert gated.attrs["tier"] == "skipped"
    assert gated.attrs["reach_rounds"] == gated.attrs["scc_rounds"] == 0
    m = mark()
    # deleting an edge inside the SCC splits it: the repair iterates
    svc._apply_chunk(np.int32([dynamic.REM_EDGE]), np.int32([2]),
                     np.int32([3]))
    (split,) = since(m, "repair.step")
    assert split.attrs["tier"] != "skipped"
    assert split.attrs["region_v"] == 6
    assert split.attrs["scc_rounds"] > 0
    assert int(svc.state.n_ccs) == NV              # all singletons now


def test_engine_lane_tiers_equal_single_tenant_tiers():
    cfg = tiny_cfg()
    knobs = dict(buckets=(8, 16), scan_lengths=(1, 4))
    eng = TenantEngine(tenant_batches=(1, 2, 3), **knobs)
    tids = ["a", "b", "c"]
    oracles = {tid: SCCService(cfg, **knobs) for tid in tids}
    for tid in tids:
        eng.create_tenant(tid, cfg)
    rng = np.random.default_rng(11)
    m = mark()
    for round_i in range(8):
        wave = []
        for tid in tids:
            if round_i and rng.random() < 0.3:
                continue
            c = rand_chunk(rng, int(rng.integers(1, 30)))
            wave.append((tid, *c))
            oracles[tid]._apply_chunk(*c)
        eng.apply_chunks(wave)
    want = {t: sum(o.repair_tier_steps[t] for o in oracles.values())
            for t in dynamic.TIER_NAMES}
    assert eng.stats()["lane_tier_steps"] == want
    waves = since(m, "engine.wave")
    assert len(waves) == 8
    for t in dynamic.TIER_NAMES:
        assert sum(w.attrs[f"tier_{t}"] for w in waves) == want[t]
    assert sum(w.attrs["lane_steps"] for w in waves) == \
        sum(o.gen for o in oracles.values())


def test_one_queue_wait_per_ticket_inside_its_submit():
    mts = MultiTenantService(tiny_cfg(), buckets=(8, 16),
                             scan_lengths=(1, 4), tenant_batches=(1, 2, 4),
                             flush_deadline_s=0.005)
    tids = [mts.create_tenant() for _ in range(4)]
    sessions = [mts.session(t) for t in tids]
    submitted0 = mts.queue.stats()["submitted"]
    m = mark()

    def drive(i):
        rng = np.random.default_rng(i)
        for _ in range(6):
            sessions[i]._apply_ops(*rand_chunk(rng, 6))

    ths = [threading.Thread(target=drive, args=(i,)) for i in range(4)]
    for th in ths:
        th.start()
    for th in ths:
        th.join(60)
    assert not any(th.is_alive() for th in ths)
    q = mts.queue.stats()
    mts.close()
    submits = {r.id: r for r in since(m, "queue.submit")}
    waits = since(m, "queue.wait")
    assert len(waits) == len(submits) == q["submitted"] - submitted0 == 24
    assert sorted(w.parent for w in waits) == sorted(submits)
    for w in waits:
        s = submits[w.parent]
        assert s.t0_ns <= w.t0_ns <= w.t1_ns <= s.t1_ns
        assert w.req == s.req
    assert abs(sum(w.t1_ns - w.t0_ns for w in waits) * 1e-9
               - q["wait_s"]) < 1e-6
