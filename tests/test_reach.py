"""Direct unit/property tests of the reachability substrate."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import reach

NV = 20


def _graph(edge_list):
    if not edge_list:
        return (jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), bool))
    src = jnp.asarray([u for u, _ in edge_list], jnp.int32)
    dst = jnp.asarray([v for _, v in edge_list], jnp.int32)
    return src, dst, jnp.ones((len(edge_list),), bool)


def _oracle_reach(edges, seeds, allowed, nv=NV):
    reach_set = {s for s in seeds if allowed[s]}
    frontier = set(reach_set)
    while frontier:
        nxt = set()
        for u, v in edges:
            if u in reach_set and allowed[u] and allowed[v] \
                    and v not in reach_set:
                nxt.add(v)
        reach_set |= nxt
        frontier = nxt
    return reach_set


EDGES = st.lists(st.tuples(st.integers(0, NV - 1), st.integers(0, NV - 1)),
                 min_size=0, max_size=50)


@settings(max_examples=25, deadline=None)
@given(EDGES, st.sets(st.integers(0, NV - 1), min_size=1, max_size=4),
       st.lists(st.booleans(), min_size=NV, max_size=NV))
def test_forward_reach_vs_oracle(edges, seeds, allowed):
    src, dst, live = _graph(edges)
    seed_m = jnp.zeros((NV,), bool).at[jnp.asarray(sorted(seeds))].set(True)
    allowed_m = jnp.asarray(allowed)
    got, _ = reach.forward_reach(src, dst, live, seed_m, allowed_m, NV + 1)
    want = _oracle_reach(edges, seeds, allowed)
    assert {i for i in range(NV) if got[i]} == want


@settings(max_examples=20, deadline=None)
@given(EDGES, st.integers(0, NV - 1), st.integers(0, NV - 1))
def test_is_reachable(edges, u, v):
    src, dst, live = _graph(edges)
    allowed = jnp.ones((NV,), bool)
    got = bool(reach.is_reachable(src, dst, live, u, v, allowed, NV + 1))
    want = v in _oracle_reach(edges, {u}, [True] * NV)
    assert got == want


@settings(max_examples=15, deadline=None)
@given(EDGES)
def test_multi_forward_reach_matches_single(edges):
    src, dst, live = _graph(edges)
    allowed = jnp.ones((NV,), bool)
    seeds = jnp.zeros((3, NV), bool).at[jnp.arange(3), jnp.arange(3)].set(
        True)
    multi, _ = reach.multi_forward_reach(src, dst, live, seeds, allowed,
                                         NV + 1)
    for b in range(3):
        single, _ = reach.forward_reach(src, dst, live, seeds[b], allowed,
                                        NV + 1)
        np.testing.assert_array_equal(np.asarray(multi[b]),
                                      np.asarray(single))


@settings(max_examples=15, deadline=None)
@given(EDGES, st.sets(st.integers(0, NV - 1), min_size=1, max_size=3),
       st.sets(st.integers(0, NV - 1), min_size=1, max_size=3))
def test_fused_equals_separate(edges, sf, sb):
    src, dst, live = _graph(edges)
    allowed = jnp.ones((NV,), bool)
    seed_f = jnp.zeros((NV,), bool).at[jnp.asarray(sorted(sf))].set(True)
    seed_b = jnp.zeros((NV,), bool).at[jnp.asarray(sorted(sb))].set(True)
    fw1, _ = reach.forward_reach(src, dst, live, seed_f, allowed, NV + 1)
    bw1, _ = reach.backward_reach(src, dst, live, seed_b, allowed, NV + 1)
    fw2, bw2, _ = reach.fused_fw_bw_reach(src, dst, live, seed_f, seed_b,
                                          allowed, NV + 1)
    np.testing.assert_array_equal(np.asarray(fw1), np.asarray(fw2))
    np.testing.assert_array_equal(np.asarray(bw1), np.asarray(bw2))


def test_priority_hash_bijective_inverse():
    v = jnp.arange(10000, dtype=jnp.int32)
    p = reach._prio(v)
    np.testing.assert_array_equal(np.asarray(reach._unprio(p)),
                                  np.asarray(v))
    assert len(np.unique(np.asarray(p))) == 10000
    assert 10000 < reach.SENT_PREIMAGE  # sentinel guard


@settings(max_examples=20, deadline=None)
@given(EDGES, st.lists(st.booleans(), min_size=NV, max_size=NV))
def test_min_prio_witness_vs_oracle(edges, alive):
    """witness[v] = argmin-priority over {u : u ⇝ v within active}."""
    src, dst, live = _graph(edges)
    active = jnp.asarray(alive)
    wit, _ = reach.propagate_min_prio(src, dst, live, active, 4 * NV)
    pri = np.asarray(reach._prio(jnp.arange(NV, dtype=jnp.int32)))
    for v in range(NV):
        if not alive[v]:
            assert int(wit[v]) == NV
            continue
        reachers = [u for u in range(NV) if alive[u] and
                    v in _oracle_reach(edges, {u}, alive)]
        want = min(reachers, key=lambda u: pri[u])
        assert int(wit[v]) == want, (v, reachers)


@settings(max_examples=20, deadline=None)
@given(EDGES)
def test_min_labels_shortcut_same_fixpoint(edges):
    src, dst, live = _graph(edges)
    allowed = jnp.ones((NV,), bool)
    labels = jnp.arange(NV, dtype=jnp.int32)
    a, _ = reach.propagate_min_labels(src, dst, live, labels, allowed,
                                      2 * NV)
    b, _ = reach.propagate_min_labels(src, dst, live, labels, allowed,
                                      2 * NV, shortcut=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
