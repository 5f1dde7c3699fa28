"""Kernel-vs-oracle differential fuzz for the sparse Pallas kernels.

frontier_expand and hash_probe back the always-on sweeps (every FW/BW
fixpoint round, every table probe), so their contract is *bit-identity*
with the ``'xla'`` oracle -- not approximate agreement.  The harness
fuzzes the kernels in interpret mode on CPU over randomized region
shapes and edge distributions (hypothesis when available, the seeded
shim otherwise) and pins the documented edge cases explicitly: empty
frontiers, duplicate edges, self-loops, all-lanes-active, and
capacity-edge shapes for frontier_expand; tombstone chains, probe
exhaustion (the ``failed`` flag), and re-adds for hash_probe.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_caches():
    # This fuzz module mints hundreds of small one-off executables on top
    # of a full-suite session that already compiled hundreds more; on the
    # CPU backend that much accumulated JIT code reproducibly segfaults
    # LLVM inside a later (tiny, otherwise-innocent) backend_compile.
    # Dropping the session's compiled-executable references first keeps
    # the fuzz sweep within the JIT's budget.  (jax.clear_caches is public
    # API; correctness is unaffected -- everything recompiles on demand.)
    jax.clear_caches()
    yield
    jax.clear_caches()

from repro.core import edge_table as et
from repro.core import reach, scc
from repro.kernels.frontier_expand import ops as fops
from repro.kernels.frontier_expand import ref as fref
from repro.kernels.hash_probe import ops as hops
from repro.kernels.hash_probe import ref as href

KERNEL = "pallas_interpret"  # the CPU-executable Pallas path
SENT = int(fref.SENTINEL)


def _eq(got, want, ctx=""):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=ctx)


# ------------------------------------------------------ frontier_expand ---

@st.composite
def frontier_case(draw):
    """(nv, dst, msg) with adversarial distributions: hot destinations
    (duplicate edges), sentinel-heavy lanes (inactive frontier), ties."""
    nv = draw(st.sampled_from([1, 2, 7, 24, 64, 128, 129, 200]))
    e = draw(st.sampled_from([0, 1, 5, 64, 255, 256, 257, 500]))
    hot = draw(st.booleans())  # all edges land on few vertices
    f = draw(st.sampled_from([1, 1, 2, 3, 9]))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    pool = min(3, nv) if hot else nv
    dst = rng.integers(0, pool, e).astype(np.int32)
    kind = draw(st.sampled_from(["dense", "sparse", "empty", "full"]))
    if kind == "empty":  # empty frontier: every message is the identity
        msg = np.full((f, e), SENT, np.uint32)
    elif kind == "full":  # all lanes active, heavy ties
        msg = rng.integers(0, 3, (f, e)).astype(np.uint32)
    elif kind == "dense":
        msg = rng.integers(0, 2**32, (f, e), dtype=np.uint64
                           ).astype(np.uint32)
    else:  # mostly-inactive lanes
        msg = np.where(rng.random((f, e)) < 0.15,
                       rng.integers(0, 2**31, (f, e), dtype=np.uint64),
                       SENT).astype(np.uint32)
    return nv, dst, msg


@given(frontier_case())
@settings(max_examples=40, deadline=None)
def test_frontier_min_matches_oracle(case):
    nv, dst, msg = case
    d = jnp.asarray(dst)
    m = jnp.asarray(msg)
    want = fref.frontier_min(d, m, nv)
    got = fops.frontier_min(d, m, nv, impl=KERNEL)
    _eq(got, want, f"nv={nv} e={dst.shape[0]} f={msg.shape[0]}")
    # the 1-D (single-frontier) entry squeezes through the same kernel
    got1 = fops.frontier_min(d, m[0], nv, impl=KERNEL)
    _eq(got1, want[0], "1-D squeeze path")


def test_frontier_min_capacity_edges():
    """Shapes ON the block boundaries (nv/e exact tile multiples, +-1)."""
    rng = np.random.default_rng(0)
    for nv in (127, 128, 129, 256):
        for e in (255, 256, 257):
            dst = jnp.asarray(rng.integers(0, nv, e), jnp.int32)
            msg = jnp.asarray(
                rng.integers(0, 2**32, e, dtype=np.uint64).astype(
                    np.uint32))
            _eq(fops.frontier_min(dst, msg, nv, impl=KERNEL),
                fref.frontier_min(dst, msg[None, :], nv)[0],
                f"nv={nv} e={e}")


def test_frontier_min_no_edges():
    out = fops.frontier_min(jnp.zeros((0,), jnp.int32),
                            jnp.zeros((0,), jnp.uint32), 17, impl=KERNEL)
    assert out.shape == (17,) and (np.asarray(out) == SENT).all()


@st.composite
def graph_case(draw):
    """Random COO graph with self-loops and duplicate edges (the edge
    table never dedupes its COO view of dead slots)."""
    nv = draw(st.sampled_from([4, 9, 24, 40]))
    e = draw(st.sampled_from([8, 40, 120]))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nv, e).astype(np.int32)
    dst = rng.integers(0, nv, e).astype(np.int32)
    loops = rng.random(e) < 0.1
    dst = np.where(loops, src, dst)  # self-loops
    if e > 4:  # duplicate edges
        src[: e // 4] = src[e // 4: 2 * (e // 4)]
        dst[: e // 4] = dst[e // 4: 2 * (e // 4)]
    live = rng.random(e) < 0.8
    allowed = rng.random(nv) < draw(st.sampled_from([0.5, 1.0]))
    seeds = rng.random(nv) < 0.2
    return (nv, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(live),
            jnp.asarray(allowed), jnp.asarray(seeds))


@given(graph_case())
@settings(max_examples=15, deadline=None)
def test_reach_sweeps_bit_identical(case):
    """Every reach.py fixpoint: kernel impl == 'xla' oracle, bit-for-bit
    (labels AND round counts -- the fixpoint must converge identically)."""
    nv, src, dst, live, allowed, seeds = case
    for impl in (KERNEL,):
        r_x, n_x = reach.forward_reach(src, dst, live, seeds, allowed, 16)
        r_k, n_k = reach.forward_reach(src, dst, live, seeds, allowed, 16,
                                       impl=impl)
        _eq(r_k, r_x, "forward_reach")
        assert int(n_k) == int(n_x)
        f_x, b_x, _ = reach.fused_fw_bw_reach(src, dst, live, seeds,
                                              seeds, allowed, 16)
        f_k, b_k, _ = reach.fused_fw_bw_reach(src, dst, live, seeds,
                                              seeds, allowed, 16,
                                              impl=impl)
        _eq(f_k, f_x, "fused fw")
        _eq(b_k, b_x, "fused bw")
        init = jnp.where(allowed, jnp.arange(nv, dtype=jnp.int32),
                         jnp.iinfo(jnp.int32).max)
        l_x, _ = reach.propagate_min_labels(src, dst, live, init, allowed,
                                            16)
        l_k, _ = reach.propagate_min_labels(src, dst, live, init, allowed,
                                            16, impl=impl)
        _eq(l_k, l_x, "propagate_min_labels")
        w_x, _ = reach.propagate_min_prio(src, dst, live, allowed, 16)
        w_k, _ = reach.propagate_min_prio(src, dst, live, allowed, 16,
                                          impl=impl)
        _eq(w_k, w_x, "propagate_min_prio")
        multi = jnp.stack([seeds, allowed & ~seeds, jnp.zeros_like(seeds)])
        m_x, _ = reach.multi_forward_reach(src, dst, live, multi, allowed,
                                           16)
        m_k, _ = reach.multi_forward_reach(src, dst, live, multi, allowed,
                                           16, impl=impl)
        _eq(m_k, m_x, "multi_forward_reach")


@given(graph_case(), st.booleans())
@settings(max_examples=8, deadline=None)
def test_scc_static_bit_identical(case, shortcut):
    nv, src, dst, live, allowed, _ = case
    want = scc.scc_static(src, dst, live, allowed, max_outer=8,
                          max_inner=16, shortcut=shortcut)
    got = scc.scc_static(src, dst, live, allowed, max_outer=8,
                         max_inner=16, shortcut=shortcut, impl=KERNEL)
    _eq(got, want, f"scc_static shortcut={shortcut}")


# ----------------------------------------------------------- hash_probe ---

# table builders for the strategy below, jitted so that a draw compiles
# once per shape: op-by-op, every insert re-traces its probe loops
_build_insert = jax.jit(et.insert, static_argnums=(3,))
_build_remove = jax.jit(et.remove, static_argnums=(3,))


@st.composite
def table_case(draw):
    """A table built through real et ops (inserts + removes => organic
    tombstone chains) plus a query batch of present/absent/removed keys."""
    cap = draw(st.sampled_from([8, 32, 64, 512]))
    seed = draw(st.integers(0, 2**31 - 1))
    load = draw(st.sampled_from([0.3, 0.7, 1.0]))  # 1.0 = saturated
    rng = np.random.default_rng(seed)
    n_ins = int(cap * load)
    u = rng.integers(0, 50, n_ins).astype(np.int32)
    v = rng.integers(0, 50, n_ins).astype(np.int32)
    table = et.empty(cap)
    table, _, _ = _build_insert(table, jnp.asarray(u), jnp.asarray(v), cap)
    # tombstone ~a third of what went in
    n_rem = max(1, n_ins // 3)
    table, _ = _build_remove(table, jnp.asarray(u[:n_rem]),
                             jnp.asarray(v[:n_rem]), cap)
    b = draw(st.sampled_from([1, 7, 33]))
    qu = rng.integers(0, 60, b).astype(np.int32)  # mix of hits/misses
    qv = rng.integers(0, 60, b).astype(np.int32)
    mp = draw(st.sampled_from(["one", "half", "cap", "over"]))
    max_probes = {"one": 1, "half": max(1, cap // 2), "cap": cap,
                  "over": 2 * cap}[mp]
    return table, jnp.asarray(qu), jnp.asarray(qv), max_probes


@given(table_case())
@settings(max_examples=30, deadline=None)
def test_hash_probe_matches_edge_table_lookup(case):
    table, qu, qv, max_probes = case
    want = et.lookup(table, qu, qv, max_probes)  # the fori-loop oracle
    got = et.lookup(table, qu, qv, max_probes, impl=KERNEL)
    _eq(got[0], want[0], f"found (cap={table.src.shape[0]}, "
                         f"max_probes={max_probes})")
    _eq(got[1], want[1], f"slot (cap={table.src.shape[0]}, "
                         f"max_probes={max_probes})")
    # and the standalone ref mirrors edge_table.lookup exactly
    base = et._hash(qu, qv, table.src.shape[0])
    rf, rs = href.probe(table.src, table.dst, table.state, base, qu, qv,
                        max_probes=max_probes)
    _eq(rf, want[0], "ref.probe found")
    _eq(rs, want[1], "ref.probe slot")


@given(table_case())
@settings(max_examples=12, deadline=None)
def test_hash_probe_insert_remove_bit_identical(case):
    """insert/remove route their membership probe through the kernel; the
    resulting tables, inserted masks, and failed flags must be identical."""
    table, qu, qv, max_probes = case
    t_x, ins_x, fail_x = et.insert(table, qu, qv, max_probes)
    t_k, ins_k, fail_k = et.insert(table, qu, qv, max_probes, impl=KERNEL)
    for a, b in zip(t_x, t_k):
        _eq(b, a, "insert table columns")
    _eq(ins_k, ins_x, "inserted mask")
    _eq(fail_k, fail_x, "failed mask")
    r_x, rem_x = et.remove(table, qu, qv, max_probes)
    r_k, rem_k = et.remove(table, qu, qv, max_probes, impl=KERNEL)
    for a, b in zip(r_x, r_k):
        _eq(b, a, "remove table columns")
    _eq(rem_k, rem_x, "removed mask")


def test_hash_probe_tombstone_chain():
    """A probe chain THROUGH a tombstone still finds the key behind it,
    and a lookup of the tombstoned key reports the tombstone slot as its
    insertion point -- under both impls."""
    cap = 16
    table = et.empty(cap)
    keys = jnp.asarray([[1, 2], [3, 4], [5, 6], [7, 8]], jnp.int32)
    table, _, _ = et.insert(table, keys[:, 0], keys[:, 1], cap)
    table, removed = et.remove(table, keys[:1, 0], keys[:1, 1], cap,
                               impl=KERNEL)
    assert bool(removed[0])
    assert int(jnp.sum(table.state == et.TOMB)) == 1
    for u, vv in ((3, 4), (5, 6), (7, 8)):  # survivors still found
        for impl in ("xla", KERNEL):
            f, _ = et.lookup(table, jnp.asarray([u]), jnp.asarray([vv]),
                             cap, impl=impl)
            assert bool(f[0]), (u, vv, impl)
    fx, sx = et.lookup(table, keys[:1, 0], keys[:1, 1], cap)
    fk, sk = et.lookup(table, keys[:1, 0], keys[:1, 1], cap, impl=KERNEL)
    assert not bool(fx[0]) and not bool(fk[0])
    assert int(sx[0]) == int(sk[0])  # same insertion point


def test_hash_probe_exhaustion_sets_failed():
    """Saturate a tiny table: overflowing lanes must raise ``failed``
    identically under both impls (the grow-and-replay trigger)."""
    cap = 8
    table = et.empty(cap)
    u = jnp.arange(2 * cap, dtype=jnp.int32)
    v = jnp.full((2 * cap,), 9, jnp.int32)
    t_x, ins_x, fail_x = et.insert(table, u, v, cap)
    t_k, ins_k, fail_k = et.insert(table, u, v, cap, impl=KERNEL)
    assert int(jnp.sum(fail_x)) == cap  # exactly the overflow
    _eq(fail_k, fail_x)
    _eq(ins_k, ins_x)
    for a, b in zip(t_x, t_k):
        _eq(b, a)
    # every lane that wanted a slot either placed or failed
    assert int(jnp.sum(ins_x) + jnp.sum(fail_x)) == 2 * cap


def test_hash_probe_readd_takes_no_slot():
    cap = 32
    table = et.empty(cap)
    u = jnp.asarray([3, 4, 5], jnp.int32)
    v = jnp.asarray([6, 7, 8], jnp.int32)
    table, ins, _ = et.insert(table, u, v, cap, impl=KERNEL)
    assert bool(ins.all())
    live_before = int(jnp.sum(table.state == et.LIVE))
    t2, ins2, fail2 = et.insert(table, u, v, cap, impl=KERNEL)
    assert not bool(ins2.any()) and not bool(fail2.any())
    assert int(jnp.sum(t2.state == et.LIVE)) == live_before
    for a, b in zip(table, t2):
        _eq(b, a, "re-add must not mutate the table")


def test_hash_probe_rehash_bit_identical():
    cap = 32
    rng = np.random.default_rng(5)
    table = et.empty(cap)
    table, _, _ = et.insert(
        table, jnp.asarray(rng.integers(0, 20, 24), jnp.int32),
        jnp.asarray(rng.integers(0, 20, 24), jnp.int32), cap)
    table, _ = et.remove(
        table, jnp.asarray(rng.integers(0, 20, 8), jnp.int32),
        jnp.asarray(rng.integers(0, 20, 8), jnp.int32), cap)
    for new_cap in (cap, 4 * cap):
        want = et.rehash(table, new_cap, new_cap)
        got = et.rehash(table, new_cap, new_cap, impl=KERNEL)
        for a, b in zip(want, got):
            _eq(b, a, f"rehash to {new_cap}")


def test_graph_config_validates_sparse_impl():
    from repro.core import graph_state as gs
    with pytest.raises(AssertionError):
        gs.GraphConfig(n_vertices=8, edge_capacity=16, sparse_impl="cuda")
    cfg = gs.GraphConfig(n_vertices=8, edge_capacity=16,
                         sparse_impl="pallas_interpret")
    assert cfg.sparse_impl == "pallas_interpret"
