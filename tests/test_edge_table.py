"""Edge-table (batched open-addressing hash set) vs a python-set oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import edge_table as et

CAP = 64
PROBES = CAP  # full-table probe bound: no spurious overflow in tests

# jitted wrappers: the oracle test applies ~1.5k single-op batches; eager
# dispatch of the probe loops dominates wall time, the jit cache makes the
# whole run a handful of compiles.
_insert = jax.jit(et.insert, static_argnames=("max_probes",))
_remove = jax.jit(et.remove, static_argnames=("max_probes",))
_lookup = jax.jit(et.lookup, static_argnames=("max_probes",))


def to_np(x):
    return np.asarray(x)


def test_insert_lookup_roundtrip():
    t = et.empty(CAP)
    u = jnp.array([1, 2, 3, 1], jnp.int32)
    v = jnp.array([9, 8, 7, 9], jnp.int32)  # (1,9) duplicated in batch
    t, ins, _ = et.insert(t, u, v, PROBES)
    assert to_np(ins).tolist() == [True, True, True, False]
    found, _ = et.lookup(t, u, v, PROBES)
    assert to_np(found).all()
    found, _ = et.lookup(t, jnp.array([9], jnp.int32),
                         jnp.array([1], jnp.int32), PROBES)
    assert not to_np(found).any()


def test_remove_and_tombstone_chain():
    t = et.empty(CAP)
    u = jnp.arange(10, dtype=jnp.int32)
    v = (u * 7 + 1) % 11
    t, ins, _ = et.insert(t, u, v, PROBES)
    assert to_np(ins).all()
    # remove half; duplicates in removal batch -> only first succeeds
    ru = jnp.array([0, 2, 4, 4], jnp.int32)
    rv = to_np(v)[[0, 2, 4, 4]]
    t, rem = et.remove(t, ru, jnp.asarray(rv), PROBES)
    assert to_np(rem).tolist() == [True, True, True, False]
    found, _ = et.lookup(t, u, v, PROBES)
    assert to_np(found).tolist() == [False, True, False, True, False,
                                     True, True, True, True, True]
    # compact rebuilds without tombstones; membership preserved
    t2 = et.compact(t, PROBES)
    found2, _ = et.lookup(t2, u, v, PROBES)
    assert to_np(found2).tolist() == to_np(found).tolist()
    live, tomb = et.fill_stats(t2)
    assert int(tomb) == 0 and int(live) == 7


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.booleans(),
                          st.integers(0, 15), st.integers(0, 15)),
                min_size=1, max_size=48))
def test_against_set_oracle(ops):
    """Random interleaving of inserts/removes == python set semantics when
    applied batch-by-batch of size 1."""
    t = et.empty(CAP)
    oracle = set()
    for is_ins, u, v in ops:
        uu = jnp.array([u], jnp.int32)
        vv = jnp.array([v], jnp.int32)
        if is_ins:
            t, okj, _ = _insert(t, uu, vv, max_probes=PROBES)
            ok = (u, v) not in oracle
            oracle.add((u, v))
        else:
            t, okj = _remove(t, uu, vv, max_probes=PROBES)
            ok = (u, v) in oracle
            oracle.discard((u, v))
        assert bool(okj[0]) == ok
    # final membership must match exactly
    all_u = jnp.array([a for a, _ in [(x, y) for x in range(16)
                                      for y in range(16)]], jnp.int32)
    all_v = jnp.array([b for _, b in [(x, y) for x in range(16)
                                      for y in range(16)]], jnp.int32)
    found, _ = _lookup(t, all_u, all_v, max_probes=PROBES)
    got = {(int(a), int(b)) for a, b, f in
           zip(to_np(all_u), to_np(all_v), to_np(found)) if f}
    assert got == oracle


def test_batch_insert_matches_sequential_order():
    """Intra-batch duplicate keys: exactly the first lane wins."""
    t = et.empty(CAP)
    u = jnp.array([5, 5, 5], jnp.int32)
    v = jnp.array([6, 6, 6], jnp.int32)
    t, ins, _ = et.insert(t, u, v, PROBES)
    assert to_np(ins).tolist() == [True, False, False]
    live, _ = et.fill_stats(t)
    assert int(live) == 1


def test_remove_incident():
    t = et.empty(CAP)
    u = jnp.array([0, 1, 2, 3], jnp.int32)
    v = jnp.array([1, 2, 3, 0], jnp.int32)
    t, _, _ = et.insert(t, u, v, PROBES)
    mask = jnp.zeros((8,), bool).at[1].set(True)
    t, _ = et.remove_incident(t, mask)
    found, _ = et.lookup(t, u, v, PROBES)
    assert to_np(found).tolist() == [False, False, True, True]


def test_overflow_reports_failure():
    t = et.empty(8)
    u = jnp.arange(16, dtype=jnp.int32)
    v = jnp.arange(16, dtype=jnp.int32) + 100
    t, ins, failed = et.insert(t, u, v, 8)
    assert int(jnp.sum(ins)) == 8  # table full: exactly capacity inserts
    # the table's own overflow report: exactly the dropped lanes, and
    # disjoint from the placed ones
    assert int(jnp.sum(failed)) == 8
    assert not bool(jnp.any(ins & failed))
    # duplicates and already-present keys are NOT overflow
    t2 = et.empty(8)
    du = jnp.array([1, 1, 1], jnp.int32)
    dv = jnp.array([2, 2, 2], jnp.int32)
    t2, ins2, failed2 = et.insert(t2, du, dv, 8)
    assert to_np(ins2).tolist() == [True, False, False]
    assert not to_np(failed2).any()
    _, _, failed3 = et.insert(t2, du, dv, 8)
    assert not to_np(failed3).any()
