"""The sorted live-edge layout (:mod:`repro.core.layout`, forced by
``sparse_impl='sorted'``) against the scatter sweeps.

On a TPU above the Pallas kernel's vertex ceiling ``'auto'`` takes the
layout: the live edges are sorted by each endpoint once per repairing
step and every round reduces sorted segments instead of scattering.  Its
contract is bit-identity with the ``'xla'`` scatter oracle, outputs and
round counts both, and a scatter fallback when more than half the
table's slots are live.  CPU ``'auto'`` never takes it, so every case
here forces it.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import smscc
from repro.core import dynamic, edge_table as et, graph_state as gs
from repro.core import layout as lay, reach, scc
from repro.core.service import SCCService

INT32_MAX = np.iinfo(np.int32).max
NV, CAP, ROUNDS = 96, 512, 200


def _table(rng, src, dst, n_live, n_tomb):
    """A ``CAP``-slot table's columns: ``n_live`` live edges and
    ``n_tomb`` tombstones (which keep their old endpoints) at random
    slots, every other slot EMPTY with endpoints 0, as the edge table
    leaves them."""
    slots = rng.permutation(CAP)
    s = np.zeros(CAP, np.int32)
    d = np.zeros(CAP, np.int32)
    state = np.zeros(CAP, np.int8)
    used = slots[:n_live + n_tomb]
    s[used], d[used] = src[:used.size], dst[:used.size]
    state[slots[:n_live]] = et.LIVE
    state[slots[n_live:n_live + n_tomb]] = et.TOMB
    return s, d, state == et.LIVE


def _graph(name):
    """(src, dst, live, allowed, seeds) of one case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n_live, n_tomb = 220, 60
    src = rng.integers(0, NV, CAP).astype(np.int32)
    dst = rng.integers(0, NV, CAP).astype(np.int32)
    allowed = rng.random(NV) < 0.85
    if name == "hub":
        # vertex 7 is an endpoint of about half of the edges, both ways
        hub = rng.random(CAP) < 0.5
        out = rng.random(CAP) < 0.5
        src = np.where(hub & out, 7, src).astype(np.int32)
        dst = np.where(hub & ~out, 7, dst).astype(np.int32)
        allowed[7] = True
    elif name == "empty_region":
        # vertices 48.. touch no edge, and half the seeds lie there
        src, dst = src % 48, dst % 48
    elif name == "over_half":
        n_live, n_tomb = CAP // 2 + 9, 40
    seeds = rng.random(NV) < 0.05
    if name == "empty_region":
        seeds[48:] = rng.random(NV - 48) < 0.2
    s, d, live = _table(rng, src, dst, n_live, n_tomb)
    return (jnp.asarray(s), jnp.asarray(d), jnp.asarray(live),
            jnp.asarray(allowed), jnp.asarray(seeds))


def _pairs(src, dst, live, allowed, seeds):
    """Each sweep on the layout beside the scatter sweeps it replaces:
    ``name -> (on_layout(layout), scatter())``."""
    vid = jnp.arange(NV, dtype=jnp.int32)
    labels = jnp.where(allowed, vid, INT32_MAX)
    unset = jnp.full((NV,), INT32_MAX, jnp.int32)
    other = jnp.roll(seeds, 3)

    def both(sweep, *args, **kw):
        a, ra = sweep(src, dst, live, *args, **kw)
        b, rb = sweep(dst, src, live, *args, **kw)
        return a, b, ra + rb

    def fw_bw():
        fw, r_fw = reach.forward_reach(src, dst, live, seeds, allowed,
                                       ROUNDS)
        bw, r_bw = reach.backward_reach(src, dst, live, other, allowed,
                                        ROUNDS)
        return fw, bw, r_fw + r_bw

    def static(shortcut):
        def run(layout):
            return scc.scc_static_rounds(
                src, dst, live, allowed, max_outer=NV + 1,
                max_inner=ROUNDS, shortcut=shortcut, layout=layout)
        return run, lambda: run(None)

    return {
        "reach_pair": (
            lambda lo: lay.reach_pair(lo, seeds, other, allowed, ROUNDS),
            fw_bw),
        "fused_reach": (
            lambda lo: lay.fused_reach(lo, seeds, other, allowed, ROUNDS),
            lambda: reach.fused_fw_bw_reach(src, dst, live, seeds, other,
                                            allowed, ROUNDS)),
        "trim": (
            lambda lo: scc.trim(src, dst, live, allowed, vid, unset, ROUNDS,
                                layout=lo),
            lambda: scc.trim(src, dst, live, allowed, vid, unset, ROUNDS)),
        "propagate_pair": (
            lambda lo: lay.propagate_pair(lo, labels, allowed, ROUNDS),
            lambda: both(reach.propagate_min_labels, labels, allowed,
                         ROUNDS)),
        "propagate_pair_shortcut": (
            lambda lo: lay.propagate_pair(lo, labels, allowed, ROUNDS,
                                          shortcut=True),
            lambda: both(reach.propagate_min_labels, labels, allowed,
                         ROUNDS, shortcut=True)),
        "prio_pair": (
            lambda lo: lay.prio_pair(lo, allowed, ROUNDS),
            lambda: both(reach.propagate_min_prio, allowed, ROUNDS)),
        "count_within": (
            lambda lo: lay.count_within(allowed, lo),
            lambda: jnp.sum(live & allowed[src] & allowed[dst])),
        "scc_static_rounds": static(False),
        "scc_static_rounds_shortcut": static(True),
    }


def _state(src, dst, live, impl):
    """A loaded, labelled state holding the graph's live edges."""
    cfg = gs.GraphConfig(n_vertices=NV, edge_capacity=CAP, max_probes=CAP,
                         max_outer=NV + 1, max_inner=ROUNDS,
                         sparse_impl=impl)
    lv = np.asarray(live)
    st = gs.from_arrays(cfg, np.asarray(src)[lv], np.asarray(dst)[lv])
    return dynamic.recompute(st, cfg), cfg


@pytest.mark.parametrize("graph", ["random", "hub", "empty_region",
                                   "over_half"])
def test_sorted_sweeps_match_the_scatter(graph):
    """Outputs and round counts of every sweep on the layout equal the
    scatter sweeps'; an update step over the same graph gives the scatter
    step's labels, acks and ``RepairStats``, and one whose table has more
    than half its slots live takes the scatter fallback and says so."""
    src, dst, live, allowed, seeds = _graph(graph)
    fits = bool(lay.fits(live))
    assert fits == (graph != "over_half")
    if fits:
        layout = jax.jit(lambda: lay.build(src, dst, live, NV))()
        for name, (on_layout, scatter) in _pairs(src, dst, live, allowed,
                                                 seeds).items():
            got = jax.jit(on_layout)(layout)
            want = jax.jit(scatter)()
            jax.tree.map(lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"{graph}: {name}"),
                got, want)

    rng = np.random.default_rng(len(graph))
    lv = np.asarray(live)
    s, d = np.asarray(src)[lv], np.asarray(dst)[lv]
    # removals of loaded edges, inserts and a vertex removal: the table
    # stays over half full in the over_half case
    kind = np.array([dynamic.REM_EDGE] * 2 + [dynamic.ADD_EDGE] * 6
                    + [dynamic.REM_VERTEX], np.int32)
    pick = rng.integers(0, s.size, 2)
    u = np.concatenate([s[pick], rng.integers(0, NV, 6), [int(s[0])]])
    v = np.concatenate([d[pick], rng.integers(0, NV, 6), [0]])
    ops = dynamic.make_ops(kind, u, v)
    out = {}
    for impl in ("sorted", "xla"):
        st, cfg = _state(src, dst, live, impl)
        new, ok, _, stats = dynamic.apply_batch_async(st, ops, cfg)
        out[impl] = (np.asarray(new.ccid), np.asarray(ok),
                     [int(x) for x in stats], int(jnp.sum(
                         new.edges.state == et.LIVE)))
    (lab_s, ok_s, rs_s, n_live), (lab_x, ok_x, rs_x, _) = (out["sorted"],
                                                           out["xla"])
    np.testing.assert_array_equal(lab_s, lab_x)
    np.testing.assert_array_equal(ok_s, ok_x)
    assert rs_s[:-1] == rs_x[:-1]
    assert rs_x[-1] == lay.LAYOUT_NONE
    assert rs_s[0] != dynamic.TIER_SKIP
    assert (n_live > CAP // 2) == (not fits)
    assert rs_s[-1] == (lay.LAYOUT_SORTED if fits else lay.LAYOUT_FALLBACK)


def test_edge_layout_holds_each_live_edge_once_in_both_orders():
    src, dst, live, _, _ = _graph("random")
    layout = lay.build(src, dst, live, NV)
    s, d, lv = map(np.asarray, (src, dst, live))
    want = sorted(zip(s[lv].tolist(), d[lv].tolist()))
    for k, flip in ((0, False), (1, True)):
        raw, ends = map(np.asarray, layout.order(k))
        tail, first = raw & INT32_MAX, raw < 0
        assert ends[0] == 0 and ends[-1] == lv.sum()
        pairs = [(int(t), h) for h in range(NV)
                 for t in tail[ends[h]:ends[h + 1]]]
        got = sorted((h, t) if flip else (t, h) for t, h in pairs)
        assert got == want
        assert first[ends[:-1][ends[1:] > ends[:-1]]].all()
        assert first[:ends[-1]].sum() == (np.diff(ends) > 0).sum()


def _chunks(rng, live_src, live_dst, n=4, b=64):
    """Op batches over the loaded graph's vertices: edge removals of
    loaded edges (splits), inserts among them (merges), vertex removals
    and re-adds."""
    out = []
    for _ in range(n):
        kind = rng.choice([dynamic.ADD_EDGE, dynamic.REM_EDGE,
                           dynamic.ADD_VERTEX, dynamic.REM_VERTEX], b,
                          p=[0.4, 0.3, 0.1, 0.2]).astype(np.int32)
        u = rng.integers(0, 300, b).astype(np.int32)
        v = rng.integers(0, 300, b).astype(np.int32)
        pick = rng.integers(0, live_src.size, b)
        rem = kind == dynamic.REM_EDGE
        u[rem], v[rem] = live_src[pick][rem], live_dst[pick][rem]
        out.append(dynamic.make_ops(kind, u, v))
    return out


def test_step_on_the_layout_at_half_a_million_vertices():
    """The whole update step with a forced ``'sorted'`` path at 2^19
    vertices and a small table: the same labels, ``ok`` and
    ``RepairStats`` as the scatter step, and the service's counter and
    ``repair.step`` events report the layout."""
    nv = 1 << 19
    base = dict(n_vertices=nv, edge_capacity=1 << 12, max_probes=64,
                max_outer=64, max_inner=256)
    cfg_s = gs.GraphConfig(**base, sparse_impl="sorted")
    cfg_x = gs.GraphConfig(**base, sparse_impl="xla")
    rng = np.random.default_rng(19)
    src = rng.integers(0, 300, 1500).astype(np.int32)
    dst = rng.integers(0, 300, 1500).astype(np.int32)
    state = gs.from_arrays(cfg_x, src, dst)
    boot = {c.sparse_impl: dynamic.recompute(state, c)
            for c in (cfg_s, cfg_x)}
    np.testing.assert_array_equal(np.asarray(boot["sorted"].ccid),
                                  np.asarray(boot["xla"].ccid))
    chunks = _chunks(rng, src, dst)
    got = {}
    for c in (cfg_s, cfg_x):
        st, rows = boot[c.sparse_impl], []
        for ops in chunks:
            st, ok, _, rs = dynamic.apply_batch_async(st, ops, c)
            rows.append((np.asarray(ok), [int(x) for x in rs]))
        got[c.sparse_impl] = (st, rows)
    (st_s, rows_s), (st_x, rows_x) = got["sorted"], got["xla"]
    for a, b in ((st_s.ccid, st_x.ccid), (st_s.n_ccs, st_x.n_ccs),
                 (st_s.v_alive, st_x.v_alive)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    repairing = 0
    for (ok_s, rs_s), (ok_x, rs_x) in zip(rows_s, rows_x):
        np.testing.assert_array_equal(ok_s, ok_x)
        assert rs_s[:-1] == rs_x[:-1]
        assert rs_x[-1] == lay.LAYOUT_NONE
        skipped = rs_s[0] == dynamic.TIER_SKIP
        repairing += not skipped
        assert rs_s[-1] == (lay.LAYOUT_NONE if skipped
                            else lay.LAYOUT_SORTED)
    assert repairing >= 2

    svc = SCCService(cfg_s, buckets=(64,), scan_lengths=(1,),
                     state=boot["sorted"])
    mark = telemetry.event("test.mark")
    for ops in chunks:
        svc._apply_chunk(*(np.asarray(x) for x in ops))
    s = svc.stats()
    assert s["repair_layout_sorted_steps"] == repairing
    assert s["repair_layout_fallback_steps"] == 0
    assert s["repair_layout_none_steps"] == len(chunks) - repairing
    steps = [r for r in telemetry.records()[0]
             if r.id > mark and r.name == "repair.step"]
    assert [r.attrs["layout"] for r in steps] == \
        ["none" if rs[0] == dynamic.TIER_SKIP else "sorted"
         for _, rs in rows_s]
    np.testing.assert_array_equal(np.asarray(svc.state.ccid),
                                  np.asarray(st_x.ccid))


def _widest_sort(fn, *args) -> int:
    """The longest sorted axis in ``fn``'s jaxpr, sub-jaxprs included (0:
    no sort).  The edge table's insert sorts its op batch; the layout
    sorts the table."""
    text = str(jax.make_jaxpr(fn)(*args))
    widths = [int(m.group(1)) for line in text.splitlines()
              if "= sort[" in line
              for m in re.finditer(r"\[(?:\d+,)*(\d+)\]",
                                   line.split("= sort[")[0])]
    return max(widths, default=0)


@pytest.mark.parametrize("shape", ["twitter_tenants", "update_1m"])
def test_auto_builds_a_layout_only_above_the_kernel_ceiling(monkeypatch,
                                                             shape):
    """Traced as a TPU resolves ``'auto'``: the tenant engine's vmapped
    step at the ego-Twitter tenant shape (1024 vertex slots, on the
    kernels) sorts nothing wider than its 64-op batch, and the
    ``update_1m`` step (2^20 vertices) sorts its whole table for the
    layout."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if shape == "twitter_tenants":
        from repro.tenancy.engine import _vmapped_scan
        cfg = smscc.config(n_vertices=1024, edge_capacity=4096,
                           max_probes=64, max_outer=64, max_inner=256)
        assert not lay.uses_layout(cfg.sparse_impl, cfg.n_vertices)
        states = jax.eval_shape(lambda: jax.tree.map(
            lambda x: jnp.stack([x] * 4), gs.empty(cfg)))
        lanes = jax.ShapeDtypeStruct((4, 1, 64), jnp.int32)
        assert _widest_sort(lambda s, o: _vmapped_scan(s, o, cfg), states,
                            dynamic.OpBatch(lanes, lanes, lanes)) == 64
    else:
        shp = smscc.SHAPES["update_1m"]
        cfg = smscc.config(n_vertices=shp["n_vertices"],
                           edge_capacity=shp["edge_capacity"])
        assert lay.uses_layout(cfg.sparse_impl, cfg.n_vertices)
        state = jax.eval_shape(lambda: gs.empty(cfg))
        lanes = jax.ShapeDtypeStruct((shp["batch"],), jnp.int32)
        assert _widest_sort(
            lambda s, o: dynamic._apply_batch_impl(s, o, cfg), state,
            dynamic.OpBatch(lanes, lanes, lanes)) == cfg.edge_capacity
