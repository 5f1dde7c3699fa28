"""SMSCC dynamic engine vs the sequential oracle (python Tarjan per op).

Covers: per-op return contracts (paper Algs 15/16/18/20), partition
correctness after arbitrary mixed batches, batch-atomicity (batched result
== sequential application in lane order), incremental merge (Fig 2) and
decremental split (Fig 3) scenarios, and the dense repair path.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import baselines, community, dynamic, graph_state as gs
from oracle import SeqSCC

NV = 16
CFG = gs.GraphConfig(n_vertices=NV, edge_capacity=256, max_probes=256,
                     max_outer=NV + 1, max_inner=NV + 2)
CFG_DENSE = gs.GraphConfig(n_vertices=NV, edge_capacity=256, max_probes=256,
                           max_outer=NV + 1, max_inner=NV + 2,
                           dense_capacity=NV)


def fresh(n_alive=NV, cfg=CFG):
    st_ = gs.empty(cfg)
    ops = dynamic.make_ops([dynamic.ADD_VERTEX] * n_alive,
                           list(range(n_alive)), [0] * n_alive)
    st_, ok = dynamic.apply_batch(st_, ops, cfg)
    assert np.asarray(ok).all()
    return st_


def labels(state):
    return np.asarray(state.ccid).tolist()


def apply_ops(state, ops_list, cfg=CFG, mode="batch"):
    ops = dynamic.make_ops([k for k, _, _ in ops_list],
                           [u for _, u, _ in ops_list],
                           [v for _, _, v in ops_list])
    if mode == "batch":
        return dynamic.apply_batch(state, ops, cfg)
    if mode == "seq":
        return baselines.sequential_apply(state, ops, cfg)
    if mode == "coarse":
        return baselines.coarse_apply(state, ops, cfg)
    raise ValueError(mode)


def test_add_vertex_contract():
    st_ = gs.empty(CFG)
    ops = [(dynamic.ADD_VERTEX, 3, 0), (dynamic.ADD_VERTEX, 3, 0),
           (dynamic.ADD_VERTEX, 5, 0)]
    st_, ok = apply_ops(st_, ops)
    assert np.asarray(ok).tolist() == [True, False, True]
    assert labels(st_)[3] == 3 and labels(st_)[5] == 5
    assert int(st_.n_ccs) == 2


def test_paper_fig2_incremental_merge():
    """AddEdge(8,3) analogue: back edge merges three chained SCCs."""
    st_ = fresh(6)
    base = [(dynamic.ADD_EDGE, u, v) for u, v in
            [(0, 1), (1, 2), (2, 0), (3, 4), (4, 3), (2, 3), (4, 5)]]
    st_, ok = apply_ops(st_, base)
    assert np.asarray(ok).all()
    assert labels(st_)[:6] == [0, 0, 0, 3, 3, 5]
    assert int(st_.n_ccs) == 3
    # the merging back edge
    st_, ok = apply_ops(st_, [(dynamic.ADD_EDGE, 5, 0)])
    assert np.asarray(ok).all()
    assert labels(st_)[:6] == [0] * 6
    assert int(st_.n_ccs) == 1


def test_paper_fig3_decremental_split():
    """RemoveEdge(8,7) analogue: one SCC breaks into two."""
    st_ = fresh(6)
    ring = [(dynamic.ADD_EDGE, u, v) for u, v in
            [(0, 1), (1, 2), (2, 3), (3, 0), (2, 0), (3, 2)]]
    st_, _ = apply_ops(st_, ring)
    assert labels(st_)[:4] == [0, 0, 0, 0]
    st_, ok = apply_ops(st_, [(dynamic.REM_EDGE, 0, 1)])
    assert bool(np.asarray(ok)[0])
    lab = labels(st_)
    # {2,3} stay strongly connected; 0 and 1 fall out
    assert lab[2] == lab[3] and lab[0] != lab[2] and lab[1] != lab[2]
    assert lab[0] != lab[1]


def test_remove_vertex_trims_edges():
    st_ = fresh(5)
    st_, _ = apply_ops(st_, [(dynamic.ADD_EDGE, u, v) for u, v in
                             [(0, 1), (1, 2), (2, 0), (2, 3), (3, 2)]])
    assert labels(st_)[:4] == [0, 0, 0, 0]
    st_, ok = apply_ops(st_, [(dynamic.REM_VERTEX, 2, 0)])
    assert bool(np.asarray(ok)[0])
    lab = labels(st_)
    assert lab[2] == NV  # dead sentinel
    assert len({lab[0], lab[1], lab[3]}) == 3  # all split
    # edges through 2 are gone: re-adding 2 restores nothing by itself
    st_, ok = apply_ops(st_, [(dynamic.ADD_VERTEX, 2, 0)])
    assert bool(np.asarray(ok)[0]) and labels(st_)[2] == 2
    assert not bool(community.check_scc(
        st_, jnp.array([0]), jnp.array([1]))[0])


def test_edge_contracts():
    st_ = fresh(3)
    ops = [(dynamic.ADD_EDGE, 0, 1),   # ok
           (dynamic.ADD_EDGE, 0, 1),   # dup in batch -> False
           (dynamic.ADD_EDGE, 0, 9),   # 9 dead -> False
           (dynamic.REM_EDGE, 1, 0)]   # absent -> False
    st_, ok = apply_ops(st_, ops)
    assert np.asarray(ok).tolist() == [True, False, False, False]
    st_, ok = apply_ops(st_, [(dynamic.REM_EDGE, 0, 1),
                              (dynamic.ADD_EDGE, 0, 1)])
    # linearization: removals before insertions -> both succeed
    assert np.asarray(ok).tolist() == [True, True]


OPS_STRATEGY = st.lists(
    st.tuples(st.integers(0, 3), st.integers(0, NV - 1),
              st.integers(0, NV - 1)),
    min_size=1, max_size=24)


@settings(max_examples=25, deadline=None)
@given(OPS_STRATEGY, st.integers(2, NV))
def test_random_history_vs_oracle(op_list, n0):
    """Sequential (B=1) application == python oracle, op by op."""
    st_ = fresh(n0)
    oracle = SeqSCC(NV)
    for i in range(n0):
        oracle.add_vertex(i)
    for kind, u, v in op_list:
        st_, ok = apply_ops(st_, [(kind, u, v)])
        if kind == dynamic.ADD_EDGE:
            want = oracle.add_edge(u, v)
        elif kind == dynamic.REM_EDGE:
            want = oracle.remove_edge(u, v)
        elif kind == dynamic.ADD_VERTEX:
            want = oracle.add_vertex(u)
        else:
            want = oracle.remove_vertex(u)
        assert bool(np.asarray(ok)[0]) == want, (kind, u, v)
        assert labels(st_) == oracle.ccid(), (kind, u, v)


@settings(max_examples=20, deadline=None)
@given(OPS_STRATEGY)
def test_batch_atomicity(op_list):
    """One batched step == the phase-ordered sequential history.

    The documented linearization: REM_VERTEX -> REM_EDGE -> ADD_VERTEX ->
    ADD_EDGE, lane order within a phase.
    """
    st_b = fresh(NV)
    st_s = fresh(NV)
    st_b, ok_b = apply_ops(st_b, op_list, mode="batch")
    phase_order = sorted(
        range(len(op_list)),
        key=lambda i: ({dynamic.REM_VERTEX: 0, dynamic.REM_EDGE: 1,
                        dynamic.ADD_VERTEX: 2, dynamic.ADD_EDGE: 3}
                       [op_list[i][0]], i))
    seq_ops = [op_list[i] for i in phase_order]
    st_s, ok_s = apply_ops(st_s, seq_ops, mode="seq")
    # same final partition
    assert labels(st_b) == labels(st_s)
    # same per-op results (reordered)
    got = np.asarray(ok_b)[phase_order].tolist()
    assert got == np.asarray(ok_s).tolist()


@settings(max_examples=10, deadline=None)
@given(OPS_STRATEGY)
def test_coarse_equals_batch_partition(op_list):
    """Coarse-grained baseline reaches the same partition sequentially."""
    st_1 = fresh(NV)
    st_2 = fresh(NV)
    st_1, _ = apply_ops(st_1, op_list, mode="seq")
    st_2, _ = apply_ops(st_2, op_list, mode="coarse")
    assert labels(st_1) == labels(st_2)


@settings(max_examples=15, deadline=None)
@given(OPS_STRATEGY)
def test_dense_path_matches_sparse(op_list):
    st_1 = fresh(NV, CFG)
    st_2 = fresh(NV, CFG_DENSE)
    st_1, ok1 = apply_ops(st_1, op_list, cfg=CFG, mode="batch")
    st_2, ok2 = apply_ops(st_2, op_list, cfg=CFG_DENSE, mode="batch")
    assert labels(st_1) == labels(st_2)
    assert np.asarray(ok1).tolist() == np.asarray(ok2).tolist()


def test_community_queries():
    st_ = fresh(6)
    st_, _ = apply_ops(st_, [(dynamic.ADD_EDGE, u, v) for u, v in
                             [(0, 1), (1, 0), (2, 3), (3, 2), (1, 2)]])
    same = community.check_scc(st_, jnp.array([0, 0, 2, 0]),
                               jnp.array([1, 2, 3, 9]))
    assert np.asarray(same).tolist() == [True, False, True, False]
    lab = community.belongs_to_community(st_, jnp.array([0, 1, 2, 3, 9]))
    assert np.asarray(lab).tolist() == [0, 0, 2, 2, NV]
    sizes = community.community_sizes(st_)
    assert int(sizes[0]) == 2 and int(sizes[2]) == 2
    rep, size = community.largest_community(st_)
    assert int(size) == 2
    pairs = community.same_community_pairs(st_, jnp.array([0, 1, 2]))
    assert np.asarray(pairs).tolist() == [[True, True, False],
                                          [True, True, False],
                                          [False, False, True]]


def test_generation_counter_and_counts():
    st_ = fresh(4)
    g0 = int(st_.gen)
    st_, _ = apply_ops(st_, [(dynamic.ADD_EDGE, 0, 1),
                             (dynamic.ADD_EDGE, 1, 0)])
    assert int(st_.gen) == g0 + 1
    assert int(st_.n_ccs) == 3  # {0,1}, {2}, {3}
    assert int(gs.live_edge_count(st_)) == 2
    assert int(gs.live_vertex_count(st_)) == 4


CFG_FUSED = gs.GraphConfig(n_vertices=NV, edge_capacity=256,
                           max_probes=256, max_outer=NV + 1,
                           max_inner=NV + 2, fuse_fwbw=True)


@settings(max_examples=15, deadline=None)
@given(OPS_STRATEGY)
def test_fused_fwbw_matches_baseline(op_list):
    """fuse_fwbw=True is a pure execution-schedule change: identical
    partitions and per-op results."""
    st_1 = fresh(NV, CFG)
    st_2 = fresh(NV, CFG_FUSED)
    st_1, ok1 = apply_ops(st_1, op_list, cfg=CFG, mode="batch")
    st_2, ok2 = apply_ops(st_2, op_list, cfg=CFG_FUSED, mode="batch")
    assert labels(st_1) == labels(st_2)
    assert np.asarray(ok1).tolist() == np.asarray(ok2).tolist()


CFG_FAST = gs.GraphConfig(n_vertices=NV, edge_capacity=256,
                          max_probes=256, max_outer=NV + 1,
                          max_inner=NV + 2, fuse_fwbw=True, shortcut=True)


@settings(max_examples=15, deadline=None)
@given(OPS_STRATEGY)
def test_shortcut_matches_baseline(op_list):
    """Pointer doubling changes rounds, never the fixpoint."""
    st_1 = fresh(NV, CFG)
    st_2 = fresh(NV, CFG_FAST)
    st_1, ok1 = apply_ops(st_1, op_list, cfg=CFG, mode="batch")
    st_2, ok2 = apply_ops(st_2, op_list, cfg=CFG_FAST, mode="batch")
    assert labels(st_1) == labels(st_2)
    assert np.asarray(ok1).tolist() == np.asarray(ok2).tolist()


def test_shortcut_reduces_rounds_on_chain():
    """A long label chain must converge in O(log n) rounds w/ doubling."""
    from repro.core import reach
    import jax.numpy as jnp
    n = 256
    src = jnp.arange(n - 1, dtype=jnp.int32)
    dst = jnp.arange(1, n, dtype=jnp.int32)
    live = jnp.ones((n - 1,), bool)
    allowed = jnp.ones((n,), bool)
    labels0 = jnp.arange(n, dtype=jnp.int32)
    _, r_plain = reach.propagate_min_labels(src, dst, live, labels0,
                                            allowed, n + 1)
    out, r_fast = reach.propagate_min_labels(src, dst, live, labels0,
                                             allowed, n + 1, shortcut=True)
    assert np.asarray(out).tolist() == [0] * n
    assert int(r_plain) >= n - 1
    assert int(r_fast) <= 12  # ~log2(256) + epsilon
