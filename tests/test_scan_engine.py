"""Fused scan-based update engine + in-graph repair gate.

Pins the PR-5 tentpole contracts:

  * the repair gate is *conservative and exact*: for random op batches the
    gated step is bit-identical to the always-repair step (labels, per-op
    results, generation, SCC count), and counting instrumentation shows
    repair really is skipped (``TIER_SKIP``) on structure-preserving
    batches -- re-adding existing edges, adding edges inside one SCC,
    removing absent edges -- while structure-changing batches never skip;
  * ``dynamic.apply_batch_scan`` (K stacked chunks through one compiled
    ``lax.scan``) equals K sequential ``apply_batch`` steps bit-exactly,
    stacked telemetry included;
  * ``BucketedScheduler.super_chunks`` covers the bucket plan with
    registry scan lengths only, padding-compatible with ``chunks``;
  * service level: the scanned pipeline equals the serial grow-and-replay
    path (and the sequential oracle) on random overflowing mixed streams,
    overflow replays only from the offending super-chunk, and the
    ``scanned_chunks`` / ``repair_skipped_steps`` telemetry reaches
    ``GraphClient.stats()``.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dynamic, graph_state as gs
from repro.core.service import SCCService
from repro.launch.stream import BucketedScheduler
from oracle import SeqSCC

NV = 24
PHASE = {dynamic.REM_VERTEX: 0, dynamic.REM_EDGE: 1,
         dynamic.ADD_VERTEX: 2, dynamic.ADD_EDGE: 3}


def cfg_pair(**kw):
    base = dict(n_vertices=NV, edge_capacity=256, max_probes=64,
                max_outer=NV + 1, max_inner=NV + 2)
    base.update(kw)
    return (gs.GraphConfig(**base, repair_gate=True),
            gs.GraphConfig(**base, repair_gate=False))


def booted(cfg):
    state = gs.all_singletons(cfg)
    return state


def step(state, op_list, cfg):
    ops = dynamic.make_ops([k for k, _, _ in op_list],
                           [u for _, u, _ in op_list],
                           [v for _, _, v in op_list])
    state, ok, ovf, rstats = dynamic.apply_batch_async(state, ops, cfg)
    return state, np.asarray(ok).tolist(), int(ovf), rstats


OPS_STRATEGY = st.lists(
    st.tuples(st.integers(0, 3), st.integers(0, NV - 1),
              st.integers(0, NV - 1)),
    min_size=1, max_size=30)


# ------------------------------------------------------- repair gate ------


@settings(max_examples=12, deadline=None)
@given(OPS_STRATEGY)
def test_gate_differential_random_mixes(op_list):
    """Gated apply_batch is bit-identical to always-repair over random
    mixed histories: labels, per-op results, overflow, gen, n_ccs."""
    cfg_g, cfg_u = cfg_pair()
    st_g, st_u = booted(cfg_g), booted(cfg_u)
    for i in range(0, len(op_list), 6):
        batch = op_list[i:i + 6]
        st_g, ok_g, ovf_g, _ = step(st_g, batch, cfg_g)
        st_u, ok_u, ovf_u, _ = step(st_u, batch, cfg_u)
        assert ok_g == ok_u, batch
        assert np.asarray(st_g.ccid).tolist() == \
            np.asarray(st_u.ccid).tolist(), batch
        assert ovf_g == ovf_u
        assert int(st_g.gen) == int(st_u.gen)
        assert int(st_g.n_ccs) == int(st_u.n_ccs)


def test_gate_skips_structure_preserving_batches():
    """Counting instrumentation: the canonical structure-preserving
    batches really skip (TIER_SKIP), structure-changing ones never do,
    and skipped steps leave the partition untouched."""
    cfg_g, cfg_u = cfg_pair()
    st_g = booted(cfg_g)
    ring = [(dynamic.ADD_EDGE, i, (i + 1) % 6) for i in range(6)]
    st_g, ok, _, rs = step(st_g, ring, cfg_g)
    assert all(ok)
    assert int(rs.tier) != dynamic.TIER_SKIP  # a merge: repair ran
    labels_before = np.asarray(st_g.ccid).tolist()

    skippers = [
        ring,                                   # re-add existing edges
        [(dynamic.ADD_EDGE, 0, 3),              # new edges inside one SCC
         (dynamic.ADD_EDGE, 4, 1)],
        [(dynamic.REM_EDGE, 7, 8)],             # remove an absent edge
        [(dynamic.REM_EDGE, 3, 0)],             # absent reverse direction
    ]
    for batch in skippers:
        prev = np.asarray(st_g.ccid).tolist()
        st_g, _, _, rs = step(st_g, batch, cfg_g)
        assert int(rs.tier) == dynamic.TIER_SKIP, batch
        assert int(rs.region_vertices) == 0 and int(rs.region_edges) == 0
        assert np.asarray(st_g.ccid).tolist() == prev, batch

    # intra-SCC chords were inserted above (graph changed, partition not)
    assert np.asarray(st_g.ccid).tolist() == labels_before

    # structure-changing batches must never skip (conservative direction)
    for batch, name in [
            ([(dynamic.REM_EDGE, 0, 1)], "intra-SCC edge removal"),
            ([(dynamic.ADD_EDGE, 10, 11)], "straddling insert"),
            ([(dynamic.REM_VERTEX, 2, 0)], "remove SCC member"),
    ]:
        st_chk = st_g
        st_chk, _, _, rs = step(st_chk, batch, cfg_g)
        assert int(rs.tier) != dynamic.TIER_SKIP, name

    # removing an isolated singleton is provably structure-preserving:
    # the gate's m_del predicate sees an empty region and skips
    st_g, ok, _, rs = step(st_g, [(dynamic.REM_VERTEX, 20, 0)], cfg_g)
    assert ok == [True]
    assert int(rs.tier) == dynamic.TIER_SKIP

    # and the ungated config reports a real tier on the very same history
    st_u = booted(cfg_u)
    st_u, _, _, rs_u = step(st_u, ring, cfg_u)
    st_u, _, _, rs_u = step(st_u, ring, cfg_u)  # re-add: empty region...
    assert int(rs_u.tier) != dynamic.TIER_SKIP  # ...but a tier still ran


# -------------------------------------------------------- scan engine -----


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 5), OPS_STRATEGY)
def test_scan_matches_sequential_steps(k, op_list):
    """apply_batch_scan over K stacked chunks == K sequential steps:
    final state, stacked ok/overflow/RepairStats, generation."""
    cfg, _ = cfg_pair(edge_capacity=64, max_probes=4)  # overflow-prone
    b = 6
    flat = (op_list * ((k * b) // len(op_list) + 1))[:k * b]
    kk = np.asarray([[o[0] for o in flat[r * b:(r + 1) * b]]
                     for r in range(k)], np.int32)
    uu = np.asarray([[o[1] for o in flat[r * b:(r + 1) * b]]
                     for r in range(k)], np.int32)
    vv = np.asarray([[o[2] for o in flat[r * b:(r + 1) * b]]
                     for r in range(k)], np.int32)
    state0 = booted(cfg)
    st_scan, ok_s, ovf_s, r_s = dynamic.apply_batch_scan(
        state0, dynamic.make_ops(kk, uu, vv), cfg)
    st_seq = state0
    oks, ovfs, repairs = [], [], []
    for r in range(k):
        st_seq, ok1, ovf1, r1 = dynamic.apply_batch_async(
            st_seq, dynamic.make_ops(kk[r], uu[r], vv[r]), cfg)
        oks.append(np.asarray(ok1))
        ovfs.append(int(ovf1))
        repairs.append([int(x) for x in r1])
    assert np.asarray(st_scan.ccid).tolist() == \
        np.asarray(st_seq.ccid).tolist()
    assert np.asarray(ok_s).tolist() == np.stack(oks).tolist()
    assert np.asarray(ovf_s).tolist() == ovfs
    # every RepairStats leaf, fixpoint round counts included
    assert np.stack([np.asarray(x) for x in r_s], 1).tolist() == repairs
    assert int(st_scan.gen) == int(st_seq.gen) == k
    assert int(st_scan.overflow) == int(st_seq.overflow)


def test_super_chunks_cover_plan_with_registry_lengths():
    """super_chunks == chunks, re-grouped: same slices in order, stacked
    rows identical to the padded per-chunk batches, group sizes from the
    scan-length registry, one bucket shape per group."""
    sched = BucketedScheduler((8, 32))
    rng = np.random.default_rng(3)
    for n in (1, 7, 8, 40, 96, 131, 256 + 8 * 5 + 3):
        kind = rng.integers(0, 4, n).astype(np.int32)
        u = rng.integers(0, NV, n).astype(np.int32)
        v = rng.integers(0, NV, n).astype(np.int32)
        flat = list(sched.chunks(kind, u, v))
        grouped = list(sched.super_chunks(kind, u, v, (1, 4)))
        assert [sl for sls, _ in grouped for sl in sls] == \
            [sl for sl, _ in flat]
        got_rows = [row for _, ops in grouped
                    for row in np.asarray(ops.kind)]
        want_rows = [np.asarray(ops.kind) for _, ops in flat]
        assert len(got_rows) == len(want_rows)
        assert all(np.array_equal(g, w)
                   for g, w in zip(got_rows, want_rows))
        plan_by_slice = {(sl.start, sl.stop): b
                         for sl, b in sched.plan(n)}
        for sls, ops in grouped:
            assert len(sls) in (1, 4)  # registry lengths only
            assert ops.kind.shape[0] == len(sls)
            for sl in sls:  # every stacked row keeps its plan bucket
                assert ops.kind.shape[1] == plan_by_slice[(sl.start,
                                                           sl.stop)]


def oracle_replay(oracle, sched, kind, u, v):
    want = np.zeros(len(kind), bool)
    for sl, _ in sched.plan(len(kind)):
        order = sorted(range(sl.start, sl.stop),
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


def test_service_scan_path_matches_serial_and_oracle():
    """Random overflowing mixed streams through the scanned pipeline, the
    serial path, and a proactively-growing service: identical per-op
    results, labels, edge sets, and generations; the oracle agrees."""
    def tiny():
        return gs.GraphConfig(n_vertices=NV, edge_capacity=32,
                              max_probes=4, max_outer=NV + 1,
                              max_inner=NV + 2)
    scan = SCCService(tiny(), buckets=(8, 16), scan_lengths=(1, 2, 4))
    serial = SCCService(tiny(), buckets=(8, 16), inflight_window=0)
    pro = SCCService(tiny(), buckets=(8, 16), scan_lengths=(1, 2, 4),
                     proactive_grow=True)
    oracle = SeqSCC(NV)
    for svc in (scan, serial, pro):
        assert svc._apply_chunk([dynamic.ADD_VERTEX] * NV, list(range(NV)),
                         [0] * NV).all()
    for i in range(NV):
        assert oracle.add_vertex(i)
    rng = np.random.default_rng(17)
    for _ in range(16):
        n = int(rng.integers(1, 64))
        is_add = rng.random(n) < 0.7
        is_vertex = rng.random(n) < 0.1
        kind = np.where(is_add,
                        np.where(is_vertex, dynamic.ADD_VERTEX,
                                 dynamic.ADD_EDGE),
                        np.where(is_vertex, dynamic.REM_VERTEX,
                                 dynamic.REM_EDGE))
        u = rng.integers(0, NV, n)
        v = rng.integers(0, NV, n)
        ok = scan._apply_chunk(kind, u, v)
        assert ok.tolist() == serial._apply_chunk(kind, u, v).tolist() \
            == pro._apply_chunk(kind, u, v).tolist()
        assert ok.tolist() == oracle_replay(oracle, scan._sched,
                                            kind, u, v).tolist()
        assert np.asarray(scan.state.ccid).tolist() == \
            np.asarray(serial.state.ccid).tolist() == \
            np.asarray(pro.state.ccid).tolist() == oracle.ccid()
        assert scan.edge_set() == serial.edge_set() == pro.edge_set() \
            == oracle.edges
        assert scan.gen == serial.gen
    # the stream exercised what it was built to exercise
    assert scan.scanned_chunks > 0 and scan.scan_dispatches > 0
    assert scan.fallback_chunks > 0  # tiny table: overflow replays ran
    assert scan.grow_count == serial.grow_count > 0


def test_overflow_replays_only_from_offending_super_chunk():
    """A chunk whose overflow sits in its SECOND super-chunk keeps the
    first super-chunk's fast-path work: results match the serial path
    bit-exactly and the resolved-clean prefix still counts as scanned."""
    def tiny():
        return gs.GraphConfig(n_vertices=NV, edge_capacity=32,
                              max_probes=64, max_outer=NV + 1,
                              max_inner=NV + 2)
    svc = SCCService(tiny(), buckets=(4,), scan_lengths=(1, 2))
    serial = SCCService(tiny(), buckets=(4,), inflight_window=0)
    for s in (svc, serial):
        assert s._apply_chunk([dynamic.ADD_VERTEX] * NV, list(range(NV)),
                       [0] * NV).all()
    # near-fill the 32-slot table (28 edges fit), then send a 16-op chunk:
    # plan [4, 4, 4, 4] -> super-chunks [2, 2].  Its first 8 ops duplicate
    # existing edges (benign), its last 8 add distinct NEW edges that
    # cannot fit (28 + 8 > 32) -- the overflow lands in the second
    # super-chunk, so the first one's fast-path work must survive.
    pairs = [(a, b) for a in range(NV) for b in range(NV) if a != b]
    fill = pairs[:28]
    ok_fill = svc._apply_chunk([dynamic.ADD_EDGE] * 28,
                        [p[0] for p in fill], [p[1] for p in fill])
    assert ok_fill.tolist() == serial._apply_chunk(
        [dynamic.ADD_EDGE] * 28, [p[0] for p in fill],
        [p[1] for p in fill]).tolist()
    assert svc.grow_count == 0, "fill phase was not supposed to overflow"
    kind = np.full(16, dynamic.ADD_EDGE, np.int32)
    u = np.asarray([p[0] for p in pairs[:8] + pairs[100:108]], np.int32)
    v = np.asarray([p[1] for p in pairs[:8] + pairs[100:108]], np.int32)
    before = svc.scanned_chunks
    ok = svc._apply_chunk(kind, u, v)
    assert ok.tolist() == serial._apply_chunk(kind, u, v).tolist()
    assert np.asarray(svc.state.ccid).tolist() == \
        np.asarray(serial.state.ccid).tolist()
    assert svc.edge_set() == serial.edge_set()
    assert svc.gen == serial.gen
    assert svc.fallback_chunks >= 1 and svc.grow_count >= 1
    # the clean first super-chunk was resolved (counted) before the
    # offending second one aborted the fast path
    assert svc.scanned_chunks == before + 2


def test_donated_abort_does_not_double_count_telemetry():
    """When a donating pipeline aborts (anchor state consumed, whole
    chunk restarts serially), the discarded fast-path prefix must not
    leave its repair/scanned telemetry behind: step counts must equal
    the serially-recorded work, exactly once per applied step."""
    import warnings

    def tiny():
        return gs.GraphConfig(n_vertices=NV, edge_capacity=32,
                              max_probes=64, max_outer=NV + 1,
                              max_inner=NV + 2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU ignores donation, warns
        donated = SCCService(tiny(), buckets=(4,), scan_lengths=(1, 2),
                             donate=True)
        serial = SCCService(tiny(), buckets=(4,), inflight_window=0)
        boot_n = 8
        pairs = [(a, b) for a in range(boot_n) for b in range(boot_n)
                 if a != b]
        fill = pairs[:28]
        extra = pairs[28:36]
        # the third chunk is 16 ops -> [2, 2] super-chunks with the
        # overflow in the SECOND one: the donated fast path's anchor was
        # consumed, so the whole chunk restarts serially -- the discarded
        # clean prefix's telemetry must not be recorded on top
        streams = [
            ([dynamic.ADD_VERTEX] * boot_n, list(range(boot_n)),
             [0] * boot_n),
            ([dynamic.ADD_EDGE] * 28, [p[0] for p in fill],
             [p[1] for p in fill]),
            ([dynamic.ADD_EDGE] * 16,
             [p[0] for p in fill[:8] + extra],
             [p[1] for p in fill[:8] + extra]),
        ]
        for kind, uu, vv in streams:
            assert donated._apply_chunk(kind, uu, vv).tolist() == \
                serial._apply_chunk(kind, uu, vv).tolist()
        assert donated.fallback_chunks >= 1
        # both services executed the identical step history after the
        # restart, so per-tier step counts must agree exactly -- the
        # aborted prefix contributes nothing
        assert donated.repair_tier_steps == serial.repair_tier_steps
        assert donated.repair_region_v_max == serial.repair_region_v_max


def test_scan_and_gate_telemetry_reach_client_stats():
    """repair_skipped_steps / scanned_chunks / scan_dispatches flow
    SCCService.stats() -> GraphClient.stats()."""
    from repro.api import AddEdge, GraphClient

    cfg = gs.GraphConfig(n_vertices=NV, edge_capacity=256, max_probes=64,
                         max_outer=NV + 1, max_inner=NV + 2)
    svc = SCCService(cfg, buckets=(8,), scan_lengths=(1, 4),
                     state=gs.all_singletons(cfg))
    client = GraphClient(svc)
    ring = [AddEdge(i, (i + 1) % 6) for i in range(6)]
    client.submit_many(ring)
    # 32 structure-preserving ops -> four 8-lane chunks -> one scan(4)
    client.submit_many((ring + ring[:2]) * 4)
    s = client.stats()
    assert s["repair_skipped_steps"] > 0
    assert s["scanned_chunks"] >= 4
    assert s["scan_dispatches"] >= 1
    assert s["fallback_chunks"] == 0
    client.close()


def test_compile_count_bounded_by_buckets_times_scan_lengths():
    """Arbitrary chunk lengths never mint step shapes beyond
    buckets x (scan lengths + serial path) per graph config."""
    cfg = gs.GraphConfig(n_vertices=NV, edge_capacity=512, max_probes=64,
                         max_outer=NV + 1, max_inner=NV + 2)
    svc = SCCService(cfg, buckets=(8, 16), scan_lengths=(1, 4),
                     state=gs.all_singletons(cfg))
    rng = np.random.default_rng(5)
    for n in (3, 8, 24, 64, 80, 31, 128, 11):
        kind = rng.choice([dynamic.ADD_EDGE] * 2 + [dynamic.REM_EDGE],
                          int(n))
        svc._apply_chunk(kind, rng.integers(0, NV, n), rng.integers(0, NV, n))
    assert svc.grow_count == 0  # capacity was generous
    bound = 2 * (2 + 1)  # buckets x (scan lengths + serial)
    assert svc.compile_count <= bound
    assert any(key[0] == "scan" for key in svc._compiled)


# ----------------------------------- sparse-kernel impl A/B (PR 7) --------


def test_service_bit_identical_across_sparse_impls():
    """The whole PR-5 scanned pipeline re-run under the Pallas sparse
    kernels (interpret mode on CPU; the same dataflow the native TPU
    impl compiles) against the XLA oracle impl on one op stream: per-op
    acks, labels, generations, edge sets, and per-tier repair step
    counts must be bit-identical.  The tiny edge table forces grow /
    rehash under the kernel impl too."""
    def mk(impl):
        cfg = gs.GraphConfig(n_vertices=NV, edge_capacity=32,
                             max_probes=4, max_outer=NV + 1,
                             max_inner=NV + 2, sparse_impl=impl)
        return SCCService(cfg, buckets=(8,), scan_lengths=(1, 2))

    pal, xla = mk("pallas_interpret"), mk("xla")
    assert pal.stats()["kernel_impl"]["frontier_expand"] \
        == "pallas_interpret"
    assert xla.stats()["kernel_impl"]["hash_probe"] == "xla"

    rng = np.random.default_rng(41)
    for s in (pal, xla):
        assert s._apply_chunk([dynamic.ADD_VERTEX] * NV, list(range(NV)),
                       [0] * NV).all()
    for step_no in range(6):
        n = int(rng.integers(4, 17))
        is_add = rng.random(n) < 0.75
        kind = np.where(is_add, dynamic.ADD_EDGE,
                        dynamic.REM_EDGE).astype(np.int32)
        u = rng.integers(0, NV, n)
        v = rng.integers(0, NV, n)
        ok_p = pal._apply_chunk(kind, u, v)
        ok_x = xla._apply_chunk(kind, u, v)
        assert ok_p.tolist() == ok_x.tolist(), step_no
        assert np.asarray(pal.state.ccid).tolist() == \
            np.asarray(xla.state.ccid).tolist(), step_no
        assert int(pal.state.n_ccs) == int(xla.state.n_ccs)
        assert pal.gen == xla.gen
    assert pal.edge_set() == xla.edge_set()
    assert pal.repair_tier_steps == xla.repair_tier_steps
    assert pal.grow_count == xla.grow_count > 0  # rehash ran under both
    # batched reachability queries agree under both impls
    qu, qv = [0, 3, 7, 22], [5, 3, 19, 1]
    assert pal.reachable(qu, qv).value.tolist() == \
        xla.reachable(qu, qv).value.tolist()


# --------------------------------------------- bulk expiry (ROADMAP 5c) ---


def test_bulk_expiry_sliding_window_matches_oracle_and_gates():
    """Sliding-window maintenance: every step inserts a fresh edge batch
    and bulk-expires the batch from W steps ago as ONE REM_EDGE chunk.
    The engine agrees with the sequential oracle throughout (acks,
    labels, edge set), and the repair gate's deletion predicate earns
    its keep on the expiry chunks specifically: expiries that only
    drop absent or intra-SCC-redundant edges skip repair (TIER_SKIP),
    expiries that break a cycle run a real tier."""
    from collections import deque

    cfg_g, _ = cfg_pair()
    svc = SCCService(cfg_g, buckets=(8,), proactive_grow=True,
                     state=gs.all_singletons(cfg_g))
    oracle = SeqSCC(NV)
    for i in range(NV):
        assert oracle.add_vertex(i)

    rng = np.random.default_rng(29)
    window, expiry_tiers = deque(), []
    for step_no in range(16):
        u = rng.integers(0, NV, 8).astype(np.int32)
        v = rng.integers(0, NV, 8).astype(np.int32)
        kind = np.full(8, dynamic.ADD_EDGE, np.int32)
        ok = svc._apply_chunk(kind, u, v)
        assert ok.tolist() == oracle_replay(oracle, svc._sched,
                                            kind, u, v).tolist(), step_no
        window.append((u, v))
        if len(window) > 3:  # the window slides: evict the oldest batch
            eu, ev = window.popleft()
            kind = np.full(8, dynamic.REM_EDGE, np.int32)
            before = dict(svc.repair_tier_steps)
            ok = svc._apply_chunk(kind, eu, ev)
            assert ok.tolist() == oracle_replay(
                oracle, svc._sched, kind, eu, ev).tolist(), step_no
            expiry_tiers.append(
                {k: svc.repair_tier_steps[k] - before[k] for k in before})
        assert np.asarray(svc.state.ccid).tolist() == oracle.ccid(), step_no
        assert svc.edge_set() == oracle.edges, step_no

    skipped = sum(d["skipped"] for d in expiry_tiers)
    real = sum(d[k] for d in expiry_tiers
               for k in ("dense", "compact", "full"))
    assert skipped > 0, "no expiry chunk was proved structure-preserving"
    assert real > 0, "no expiry chunk ran a real repair tier"
