"""Tiered region-compacted repair engine vs the full-sparse baseline.

Covers the tentpole contracts of the tiered dispatcher in
``dynamic._apply_batch_impl`` phase 5:

  * differential: dense / compact-sparse / full-sparse tiers are
    bit-identical to the untiered full-sparse path over random op mixes;
  * tier selection is monotone in region size and degrades cleanly to the
    full sweep on edge-capacity overflow;
  * the dense tier genuinely feeds the injected ``reach_blockmm``
    boolean mat-mul (Pallas) and its products agree with the jnp fallback
    on random regions;
  * the per-step telemetry (tier, region vertex/edge counts) reaches
    ``SCCService.stats()`` and ``GraphClient.stats()``.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dynamic, graph_state as gs, scc
from repro.kernels import reach_blockmm as rb

NV = 32
_BASE = dict(n_vertices=NV, edge_capacity=256, max_probes=256,
             max_outer=NV + 1, max_inner=NV + 2)
CFG_FULL = gs.GraphConfig(**_BASE)
# compact tier only (regions of <= 16 vertices, 8/64 edge-slot buckets)
CFG_COMPACT = gs.GraphConfig(**_BASE, region_vertex_capacity=16,
                             region_edge_buckets=(8, 64))
# all three tiers; the dense tier runs the Pallas kernel in interpret mode
CFG_TIERED = gs.GraphConfig(**_BASE, dense_capacity=8,
                            dense_matmul_impl="pallas_interpret",
                            region_vertex_capacity=16,
                            region_edge_buckets=(8, 64))
# compact tier whose edge registry is easy to overflow (vertices fit,
# edges do not)
CFG_TINY_EDGES = gs.GraphConfig(**_BASE, region_vertex_capacity=16,
                                region_edge_buckets=(8,))


def fresh(cfg):
    st_ = gs.empty(cfg)
    ops = dynamic.make_ops([dynamic.ADD_VERTEX] * NV, list(range(NV)),
                           [0] * NV)
    st_, ok = dynamic.apply_batch(st_, ops, cfg)
    assert np.asarray(ok).all()
    return st_


def labels(state):
    return np.asarray(state.ccid).tolist()


def step(state, op_list, cfg):
    ops = dynamic.make_ops([k for k, _, _ in op_list],
                           [u for _, u, _ in op_list],
                           [v for _, _, v in op_list])
    state, ok, _, rstats = dynamic.apply_batch_async(state, ops, cfg)
    return state, np.asarray(ok).tolist(), rstats


def cycle_ops(ids):
    return [(dynamic.ADD_EDGE, ids[i], ids[(i + 1) % len(ids)])
            for i in range(len(ids))]


OPS_STRATEGY = st.lists(
    st.tuples(st.integers(0, 3), st.integers(0, NV - 1),
              st.integers(0, NV - 1)),
    min_size=1, max_size=24)


@settings(max_examples=15, deadline=None)
@given(OPS_STRATEGY)
def test_tiers_differential_random_mixes(op_list):
    """Every tier config reproduces the untiered path bit-exactly, per-op
    results included, over random mixed histories."""
    states = {cfg: fresh(cfg) for cfg in (CFG_FULL, CFG_COMPACT,
                                          CFG_TIERED, CFG_TINY_EDGES)}
    for i in range(0, len(op_list), 6):
        batch = op_list[i:i + 6]
        outs = {}
        for cfg in states:
            states[cfg], ok, _ = step(states[cfg], batch, cfg)
            outs[cfg] = (labels(states[cfg]), ok)
        want = outs[CFG_FULL]
        for cfg in (CFG_COMPACT, CFG_TIERED, CFG_TINY_EDGES):
            assert outs[cfg] == want, batch


def test_all_three_tiers_fire_and_agree():
    """Growing cycle merges walk the dispatcher through dense -> compact
    -> full, each bit-identical to the untiered baseline."""
    want_tier = {4: dynamic.TIER_DENSE, 12: dynamic.TIER_COMPACT,
                 20: dynamic.TIER_FULL}
    for k, want in want_tier.items():
        s_full = fresh(CFG_FULL)
        s_tier = fresh(CFG_TIERED)
        s_full, ok_full, _ = step(s_full, cycle_ops(list(range(k))),
                                  CFG_FULL)
        s_tier, ok_tier, rstats = step(s_tier, cycle_ops(list(range(k))),
                                       CFG_TIERED)
        assert int(rstats.tier) == want, k
        assert int(rstats.region_vertices) == k
        assert int(rstats.region_edges) == k
        assert labels(s_full) == labels(s_tier)
        assert ok_full == ok_tier


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(2, NV - 1), min_size=2, max_size=5))
def test_tier_selection_monotone_in_region_size(sizes):
    """A strictly larger affected region never selects a smaller tier."""
    picks = []
    for k in sorted(set(sizes)):
        s = fresh(CFG_TIERED)
        _, _, rstats = step(s, cycle_ops(list(range(k))), CFG_TIERED)
        picks.append((k, int(rstats.tier)))
    tiers = [t for _, t in picks]
    assert tiers == sorted(tiers), picks


def test_edge_capacity_overflow_falls_back_to_full():
    """Region vertices fit the compact tier but its edge registry cannot
    hold the live intra-region edges: dispatch must degrade to the full
    sweep and still produce the exact partition."""
    k4 = [(dynamic.ADD_EDGE, u, v) for u in range(4) for v in range(4)
          if u != v]  # 12 edges > the 8-slot registry of CFG_TINY_EDGES
    s_full = fresh(CFG_FULL)
    s_tiny = fresh(CFG_TINY_EDGES)
    s_full, _, _ = step(s_full, k4, CFG_FULL)
    s_tiny, _, rstats = step(s_tiny, k4, CFG_TINY_EDGES)
    assert int(rstats.tier) == dynamic.TIER_FULL
    assert int(rstats.region_vertices) == 4  # fits vcap; edges overflowed
    assert int(rstats.region_edges) == 12
    assert labels(s_full) == labels(s_tiny)


def test_compact_region_roundtrip_labels():
    """scc_compact_region == scc_static on the same region mask (the
    bit-identity the compact tier relies on), across random graphs."""
    rng = np.random.default_rng(7)
    for trial in range(5):
        e = rng.integers(0, NV, (70, 2))
        src = jnp.asarray(e[:, 0], jnp.int32)
        dst = jnp.asarray(e[:, 1], jnp.int32)
        live = jnp.asarray(rng.random(70) < 0.9)
        region = jnp.asarray(rng.random(NV) < 0.6)
        want = scc.scc_static(src, dst, live, region, max_outer=NV,
                              max_inner=NV + 2)
        got, fits, _ = scc.scc_compact_region(
            src, dst, live, region, NV, 128, max_outer=NV,
            max_inner=NV + 2)
        assert bool(fits)
        np.testing.assert_array_equal(
            np.where(np.asarray(region), np.asarray(got), 0),
            np.where(np.asarray(region), np.asarray(want), 0))


def test_compact_region_preserves_unassigned_sentinel():
    """When max_outer is exhausted mid-region, slots scc_static left
    unassigned must surface as the INT32_MAX sentinel from the compact
    tier too -- never a clipped real vertex id."""
    # two SCC layers: cycle {0,1} -> cycle {2,3}; max_outer=1 assigns only
    # the source layer and must leave {2,3} at the sentinel
    src = jnp.array([0, 1, 2, 3, 1], jnp.int32)
    dst = jnp.array([1, 0, 3, 2, 2], jnp.int32)
    live = jnp.ones((5,), bool)
    region = jnp.zeros((NV,), bool).at[:4].set(True)
    want = scc.scc_static(src, dst, live, region, max_outer=1,
                          max_inner=NV)
    got, fits, _ = scc.scc_compact_region(src, dst, live, region, 16, 16,
                                          max_outer=1, max_inner=NV)
    assert bool(fits)
    np.testing.assert_array_equal(np.asarray(got)[:4], np.asarray(want)[:4])
    sent = np.iinfo(np.int32).max
    assert np.asarray(want)[2] == sent  # the scenario really starves


def test_injected_matmul_matches_fallback_on_random_regions():
    """Satellite: the Pallas product the dense tier now feeds agrees with
    the jnp fallback product on random region adjacencies."""
    rng = np.random.default_rng(3)
    for trial in range(4):
        e = rng.integers(0, NV, (60, 2))
        src = jnp.asarray(e[:, 0], jnp.int32)
        dst = jnp.asarray(e[:, 1], jnp.int32)
        live = jnp.ones((60,), bool)
        region = jnp.asarray(rng.random(NV) < 0.5)

        def injected(a, b):
            return rb.bool_matmul(a, b, block=32, impl="pallas_interpret")

        lab_k, fits = scc.scc_dense_region(src, dst, live, region, NV,
                                           matmul=injected)
        lab_j, _ = scc.scc_dense_region(src, dst, live, region, NV)
        assert bool(fits)
        np.testing.assert_array_equal(np.asarray(lab_k), np.asarray(lab_j))
        # and the raw closure products themselves
        adj, _, _, _ = scc.gather_region(src, dst, live, region, NV)
        np.testing.assert_array_equal(
            np.asarray(scc.closure_dense(adj, injected)),
            np.asarray(scc.closure_dense(adj, None)))


def test_dense_tier_runs_injected_kernel_product():
    """The dense tier's labels under the tiered config (Pallas product)
    equal the labels under an identical config forced onto the jnp oracle
    product -- the kernel is genuinely in the dataflow, not bypassed."""
    cfg_xla = gs.GraphConfig(**_BASE, dense_capacity=8,
                             dense_matmul_impl="xla",
                             region_vertex_capacity=16,
                             region_edge_buckets=(8, 64))
    s_pallas = fresh(CFG_TIERED)
    s_xla = fresh(cfg_xla)
    ops = cycle_ops(list(range(5)))
    s_pallas, _, rs1 = step(s_pallas, ops, CFG_TIERED)
    s_xla, _, rs2 = step(s_xla, ops, cfg_xla)
    assert int(rs1.tier) == int(rs2.tier) == dynamic.TIER_DENSE
    assert labels(s_pallas) == labels(s_xla)


def test_service_and_client_surface_tier_telemetry():
    """Per-step tier telemetry flows SCCService.stats() -> GraphClient."""
    from repro.api import AddEdge, GraphClient
    from repro.core.service import SCCService

    svc = SCCService(CFG_TIERED, buckets=(8, 32),
                     state=gs.all_singletons(CFG_TIERED))
    client = GraphClient(svc)
    client.submit_many([AddEdge(u, (u + 1) % 4) for u in range(4)])  # dense
    client.submit_many(
        [AddEdge(u, (u + 1) % 12) for u in range(12)])  # compact
    client.submit_many(
        [AddEdge(u, (u + 1) % 20) for u in range(20)])  # full
    s = client.stats()
    assert s["repair_dense_steps"] >= 1
    assert s["repair_compact_steps"] >= 1
    assert s["repair_full_steps"] >= 1
    n_steps = (s["repair_dense_steps"] + s["repair_compact_steps"]
               + s["repair_full_steps"])
    assert n_steps >= 3  # one per bucket batch, replay batches included
    assert s["repair_region_v_max"] == 20
    assert s["repair_region_e_max"] >= 20  # final merge sees the whole ring
    client.close()
