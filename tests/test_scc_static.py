"""Static parallel SCC (trim + coloring) vs the python Tarjan oracle."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import scc
from oracle import tarjan_ccid

NV = 24
MAXI = NV + 2


def run_scc(edges, nv=NV, active=None):
    src = jnp.array([u for u, _ in edges] + [0], jnp.int32)[:max(len(edges), 1)]
    dst = jnp.array([v for _, v in edges] + [0], jnp.int32)[:max(len(edges), 1)]
    if not edges:
        src = jnp.zeros((1,), jnp.int32)
        dst = jnp.zeros((1,), jnp.int32)
        live = jnp.zeros((1,), bool)
    else:
        live = jnp.ones((len(edges),), bool)
    if active is None:
        active = jnp.ones((nv,), bool)
    lab = scc.scc_static(src, dst, live, active,
                         max_outer=nv, max_inner=MAXI)
    return np.asarray(lab)


def canon(lab, active=None, nv=NV):
    out = []
    for i, l in enumerate(lab):
        if active is not None and not active[i]:
            out.append(nv)
        else:
            out.append(int(l))
    return out


def test_paper_fig1():
    """Fig 1(a): three SCCs -- {8,9,10} pattern recreated as labelled sets."""
    # SCC A = {0,1,2} cycle, SCC B = {3,4} cycle, SCC C = {5}, A->B->C chain
    edges = [(0, 1), (1, 2), (2, 0), (3, 4), (4, 3), (2, 3), (4, 5)]
    lab = run_scc(edges, nv=6)
    assert lab[:6].tolist() == [0, 0, 0, 3, 3, 5]


def test_paper_fig2_addedge_merge():
    """Fig 2: adding (8,3)-style back edge merges all three SCCs."""
    edges = [(0, 1), (1, 2), (2, 0), (3, 4), (4, 3), (2, 3), (4, 5), (5, 0)]
    lab = run_scc(edges, nv=6)
    assert lab[:6].tolist() == [0] * 6


def test_empty_and_singletons():
    lab = run_scc([], nv=4)
    assert lab[:4].tolist() == [0, 1, 2, 3]


def test_masked_region_only():
    """Inactive vertices must not relay reachability (limited sweep)."""
    # 0 -> 1 -> 2 -> 0 but 1 inactive: no cycle within active set
    edges = [(0, 1), (1, 2), (2, 0)]
    active = jnp.array([True, False, True] + [True] * (NV - 3))
    lab = run_scc(edges, active=active)
    assert lab[0] == 0 and lab[2] == 2
    assert lab[1] == np.iinfo(np.int32).max  # sentinel for inactive


def test_long_cycle_and_tail():
    n = 20
    cyc = [(i, (i + 1) % 12) for i in range(12)]          # 12-cycle
    tail = [(i, i + 1) for i in range(12, n - 1)]          # DAG tail
    lab = run_scc(cyc + tail + [(11, 12)], nv=n)
    assert lab[:12].tolist() == [0] * 12
    assert lab[12:n].tolist() == list(range(12, n))


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, NV - 1), st.integers(0, NV - 1)),
                min_size=0, max_size=80))
def test_random_vs_tarjan(edge_list):
    edges = list(dict.fromkeys(edge_list))  # dedupe, keep order
    lab = run_scc(edges)
    want = tarjan_ccid(NV, edges)
    assert lab[:NV].tolist() == want


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, NV - 1), st.integers(0, NV - 1)),
                min_size=0, max_size=60),
       st.lists(st.booleans(), min_size=NV, max_size=NV))
def test_random_masked_vs_tarjan(edge_list, alive):
    edges = list(dict.fromkeys(edge_list))
    active = jnp.array(alive)
    lab = run_scc(edges, active=active)
    want = tarjan_ccid(NV, edges, alive)
    got = [int(l) if alive[i] else NV
           for i, l in enumerate(lab[:NV])]
    want = [w if alive[i] else NV for i, w in enumerate(want)]
    assert got == want


def test_dense_region_matches_sparse():
    rng = np.random.default_rng(0)
    for trial in range(5):
        e = rng.integers(0, NV, (60, 2))
        edges = [(int(a), int(b)) for a, b in e]
        src = jnp.array([u for u, _ in edges], jnp.int32)
        dst = jnp.array([v for _, v in edges], jnp.int32)
        live = jnp.ones((len(edges),), bool)
        region = jnp.asarray(rng.random(NV) < 0.7)
        sparse = scc.scc_static(src, dst, live, region,
                                max_outer=NV, max_inner=MAXI)
        dense, fits = scc.scc_dense_region(src, dst, live, region, NV)
        assert bool(fits)
        np.testing.assert_array_equal(
            np.where(np.asarray(region), np.asarray(dense), 0),
            np.where(np.asarray(region), np.asarray(sparse), 0))
