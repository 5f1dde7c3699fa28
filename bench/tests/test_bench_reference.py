"""The benchmark's reference against the repo's sequential test oracle,
and the generators' determinism."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from bench import generators as gen
from bench import reference, workload

ORACLE = Path(__file__).resolve().parents[2] / "tests" / "oracle.py"
ABCD = (0.57, 0.19, 0.19, 0.05)


def _oracle():
    spec = importlib.util.spec_from_file_location("bench_seq_oracle",
                                                  ORACLE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _seq_apply(seq, kind, u, v):
    """One batch through the sequential oracle in the batch's
    linearization: RemoveVertex, RemoveEdge, AddVertex, AddEdge, each
    in lane order."""
    ok = np.zeros(kind.shape[0], bool)
    for phase in (gen.REM_VERTEX, gen.REM_EDGE, gen.ADD_VERTEX,
                  gen.ADD_EDGE):
        for i in np.nonzero(kind == phase)[0]:
            a, b = int(u[i]), int(v[i])
            if phase == gen.REM_VERTEX:
                ok[i] = seq.remove_vertex(a)
            elif phase == gen.REM_EDGE:
                ok[i] = seq.remove_edge(a, b)
            elif phase == gen.ADD_VERTEX:
                ok[i] = seq.add_vertex(a)
            else:
                ok[i] = seq.add_edge(a, b)
    return ok


@pytest.mark.parametrize("seed", [0, 1, 2 ** 40 + 3])
def test_host_graph_matches_sequential_oracle(seed):
    oracle = _oracle()
    nv = 24
    rng = np.random.default_rng(seed)
    seq = oracle.SeqSCC(nv)
    host = reference.HostGraph(nv, np.zeros(nv, bool), np.zeros(0))
    for step in range(12):
        n = 40
        kind = rng.integers(0, 4, n).astype(np.int32)
        if step == 0:
            kind[:] = gen.ADD_VERTEX
        # some out-of-range ids: both sides must refuse them
        u = rng.integers(-1, nv + 1, n)
        v = rng.integers(-1, nv + 1, n)
        v[(kind == gen.ADD_VERTEX) | (kind == gen.REM_VERTEX)] = 0
        want = _seq_apply(seq, kind, u, v)
        got = host.apply(kind, u, v)
        assert np.array_equal(got, want), step
        assert host.alive.tolist() == seq.alive
        assert set(zip((host.keys // nv).tolist(),
                       (host.keys % nv).tolist())) == seq.edges
        lab = host.labels()
        assert lab.tolist() == seq.ccid()
        a, b = rng.integers(0, nv, 16), rng.integers(0, nv, 16)
        assert reference.same_scc(host, lab, a, b).tolist() == [
            seq.check_scc(int(x), int(y)) for x, y in zip(a, b)]


def test_state_gaps_count_each_kind_of_difference():
    nv = 8
    host = reference.HostGraph(nv, np.ones(nv, bool),
                               np.array([0 * nv + 1, 1 * nv + 0]))
    lab = host.labels()
    keys = host.keys.copy()
    clean = reference.state_gaps(host, host.alive, lab, keys, nv - 1)
    assert clean == {"alive_differ": 0, "edges_differ": 0,
                     "labels_differ": 0, "n_ccs_differ": 0}
    bad = lab.copy()
    bad[1] = 1
    gaps = reference.state_gaps(host, host.alive, bad, keys[:1], nv)
    assert gaps == {"alive_differ": 0, "edges_differ": 1,
                    "labels_differ": 1, "n_ccs_differ": 1}


@pytest.mark.parametrize("seed", [7, 2 ** 33 + 5])
def test_rmat_is_exact_distinct_loop_free_and_seeded(seed):
    n = 1000  # not a power of two: ids past it are drawn again
    src, dst = gen.rmat_edges(seed, n, 1817, ABCD)
    assert src.size == dst.size == 1817
    assert not np.any(src == dst)
    assert src.min() >= 0 and max(src.max(), dst.max()) < n
    keys = src.astype(np.int64) * n + dst
    assert np.unique(keys).size == 1817
    again = gen.rmat_edges(seed, n, 1817, ABCD)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])
    other = gen.rmat_edges(seed + 1, n, 1817, ABCD)
    assert not np.array_equal(src, other[0])
    # skew: the busiest vertex carries far more than the mean degree
    assert np.bincount(src, minlength=n).max() > 10 * 1817 / n
    u, v = gen.rmat_vertices(gen.rng_for(seed, 3), 5000, ABCD,
                             gen.vertex_perm(seed, n))
    assert u.size == v.size == 5000
    assert min(u.min(), v.min()) >= 0 and max(u.max(), v.max()) < n


def test_zipf_weights():
    w = gen.zipf_weights(128, 1.0)
    assert w.sum() == pytest.approx(1.0)
    assert w[0] == pytest.approx(2 * w[1])


def _shape(seed, i=0):
    src, dst = gen.rmat_edges(seed, 200, 300, ABCD, stream=i)
    return workload.GraphShape(256, ABCD, gen.vertex_perm(seed, 200, i),
                               src, dst)


def test_session_pools_are_a_function_of_seed_and_session():
    traffic = {"sessions": 2, "pool": 50,
               "tenant_popularity": {"zipf_s": 1.0},
               "requests": [{"kind": "read", "share": 0.8,
                             "queries": {"same_scc": 4, "community_of": 4}},
                            {"kind": "update", "share": 0.2, "ops": 64}],
               "update_mix": {"insert_share": 0.5, "vertex_share": 0.2,
                              "edge_endpoints": "rmat",
                              "vertex_endpoints": "uniform"}}
    graphs = [_shape(5, i) for i in range(4)]

    def flat(pool):
        out = []
        for r in pool:
            out.append((r.kind, r.graph))
            arrs = r.ops if r.kind == "update" else [
                a for q in r.queries.values() for a in q]
            out.extend(a.tolist() for a in arrs)
        return out

    a = workload.session_pool(traffic, graphs, 2 ** 41, 0)
    assert flat(a) == flat(workload.session_pool(traffic, graphs, 2 ** 41,
                                                 0))
    assert flat(a) != flat(workload.session_pool(traffic, graphs, 2 ** 41,
                                                 1))
    kinds = [r.kind for r in a]
    # the mix is exact in every block of five, whatever the seed
    for pool in (a, workload.session_pool(traffic, graphs, 7, 1)):
        for i in range(0, 50, 5):
            assert [r.kind for r in pool[i:i + 5]].count("update") == 1
    assert kinds != [r.kind for r in workload.session_pool(
        traffic, graphs, 7, 1)]
    ups = [r for r in a if r.kind == "update"]
    for r in ups:
        kind, u, v = r.ops
        assert kind.shape == (64,)
        vertex = (kind == gen.ADD_VERTEX) | (kind == gen.REM_VERTEX)
        assert np.all(v[vertex] == 0)
        assert np.all((u >= 0) & (u < 256) & (v >= 0) & (v < 256))
    # removals name loaded edges
    g = graphs[ups[0].graph]
    kind, u, v = ups[0].ops
    loaded = set(zip(g.loaded_src.tolist(), g.loaded_dst.tolist()))
    rem = kind == gen.REM_EDGE
    assert set(zip(u[rem].tolist(), v[rem].tolist())) <= loaded

