"""Whole-lane dense repair and bulk tenant provisioning.

With ``dense_capacity >= n_vertices`` every region fits the dense tier, so
phase 5 of the step is one dense closure of the live graph: no FW/BW
sweep, no sparse tier.  These tests hold it to the sequential oracle and,
bit for bit, to the tiered path (``dense_capacity`` 0), in
:class:`SCCService` and under the tenant engine's ``vmap``, with both the
jnp product and the Pallas kernel in interpret mode; they check that the
step's program holds no sparse fixpoint; and they hold
:meth:`TenantEngine.create_tenants` to as many ``create_tenant`` calls.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import dynamic, graph_state as gs
from repro.core.service import SCCService
from repro.tenancy import MultiTenantService, TenantEngine
from repro.tenancy import engine as engine_mod

from oracle import SeqSCC, tarjan_ccid

NV = 64
ALIVE = 56                     # slots past it stay free for AddVertex
N_EDGES = 480                  # 15% of the ordered pairs of 56 vertices
KNOBS = dict(buckets=(16, 64), scan_lengths=(1, 4))
IMPLS = ("xla", "pallas_interpret")


def cfg_for(dense_capacity: int, impl: str = "xla") -> gs.GraphConfig:
    return gs.GraphConfig(n_vertices=NV, edge_capacity=2048, max_probes=64,
                          max_outer=NV + 1, max_inner=NV + 2,
                          dense_capacity=dense_capacity,
                          dense_matmul_impl=impl,
                          region_vertex_capacity=32,
                          region_edge_buckets=(256, 1024))


def dense_graph(seed: int):
    """``N_EDGES`` distinct non-loop edges among the first ``ALIVE`` ids."""
    rng = np.random.default_rng(seed)
    keys = rng.choice(ALIVE * ALIVE, 2 * N_EDGES, replace=False)
    keys = keys[keys // ALIVE != keys % ALIVE][:N_EDGES]
    return ((keys // ALIVE).astype(np.int32),
            (keys % ALIVE).astype(np.int32))


def built(cfg: gs.GraphConfig, seed: int) -> gs.GraphState:
    """A loaded, labelled graph at generation 0."""
    src, dst = dense_graph(seed)
    st = dynamic.recompute(gs.from_arrays(cfg, src, dst, ALIVE), cfg)
    return st._replace(gen=jnp.zeros_like(st.gen))


def op_chunk(rng, n: int):
    """Edge churn among the live ids and vertex ops over every slot."""
    kind = rng.choice([dynamic.ADD_EDGE, dynamic.ADD_EDGE, dynamic.REM_EDGE,
                       dynamic.REM_EDGE, dynamic.ADD_VERTEX,
                       dynamic.REM_VERTEX], size=n).astype(np.int32)
    u = rng.integers(0, NV, n).astype(np.int32)
    v = rng.integers(0, NV, n).astype(np.int32)
    return kind, u, v


def oracle_for(seed: int) -> SeqSCC:
    o = SeqSCC(NV)
    for u in range(ALIVE):
        o.add_vertex(u)
    for u, v in zip(*dense_graph(seed)):
        o.add_edge(int(u), int(v))
    return o


PHASE = {dynamic.REM_VERTEX: 0, dynamic.REM_EDGE: 1, dynamic.ADD_VERTEX: 2,
         dynamic.ADD_EDGE: 3}


def oracle_apply(o: SeqSCC, kind, u, v) -> np.ndarray:
    """A chunk as the scheduler cuts it into steps (64-op steps when it
    fills one, else 16-op ones), each step in its linearization:
    RemoveVertex, RemoveEdge, AddVertex, AddEdge, each in lane order."""
    n = kind.shape[0]
    step = 64 if n >= 64 else 16
    ok = np.zeros(n, bool)
    for start in range(0, n, step):
        lanes = range(start, min(start + step, n))
        for i in sorted(lanes, key=lambda i: (PHASE[int(kind[i])], i)):
            k, a, b = int(kind[i]), int(u[i]), int(v[i])
            ok[i] = {dynamic.ADD_EDGE: lambda: o.add_edge(a, b),
                     dynamic.REM_EDGE: lambda: o.remove_edge(a, b),
                     dynamic.ADD_VERTEX: lambda: o.add_vertex(a),
                     dynamic.REM_VERTEX: lambda: o.remove_vertex(a)}[k]()
    return ok


def n_ccs(ccid) -> int:
    lab = np.asarray(ccid)
    return int(np.sum(lab == np.arange(NV)))


@pytest.mark.parametrize("impl", IMPLS)
def test_service_dense_lane_matches_oracle_and_tiers(impl):
    seed = 11
    dense = SCCService(cfg_for(NV, impl), state=built(cfg_for(NV, impl),
                                                      seed), **KNOBS)
    tiered = SCCService(cfg_for(0), state=built(cfg_for(0), seed), **KNOBS)
    oracle = oracle_for(seed)
    rng = np.random.default_rng(seed)
    for n in (16, 16, 40, 64, 16, 128):
        kind, u, v = op_chunk(rng, n)
        ok_d, gen_d = dense._apply_ops(kind, u, v)
        ok_t, gen_t = tiered._apply_ops(kind, u, v)
        ok_o = oracle_apply(oracle, kind, u, v)
        assert np.array_equal(np.asarray(ok_d), np.asarray(ok_t))
        assert np.array_equal(np.asarray(ok_d), ok_o)
        assert gen_d == gen_t
        lab = np.asarray(dense.state.ccid)
        assert np.array_equal(lab, np.asarray(tiered.state.ccid))
        assert lab.tolist() == oracle.ccid()
        assert int(dense.state.n_ccs) == int(tiered.state.n_ccs) \
            == n_ccs(oracle.ccid())
    tiers = dense.repair_tier_steps
    assert tiers["dense"] > 0
    assert tiers["compact"] == tiers["full"] == 0
    assert dense.edge_set() == tiered.edge_set() == oracle.edges


@pytest.mark.parametrize("impl", IMPLS)
def test_engine_dense_lanes_match_oracle_and_tiers(impl):
    """Four lanes under ``vmap``, every lane repaired by its closure."""
    seeds = (21, 22, 23, 24)
    cfg_d, cfg_t = cfg_for(NV, impl), cfg_for(0)
    eng = TenantEngine(tenant_batches=(1, 2, 4), **KNOBS)
    eng.create_tenants([(f"t{s}", cfg_d, built(cfg_d, s)) for s in seeds])
    tiered = {s: SCCService(cfg_t, state=built(cfg_t, s), **KNOBS)
              for s in seeds}
    oracles = {s: oracle_for(s) for s in seeds}
    rng = np.random.default_rng(5)
    for wave in range(5):
        chunks = {s: op_chunk(rng, 16 if wave % 2 else 64) for s in seeds}
        out = eng.apply_chunks([(f"t{s}", *c) for s, c in chunks.items()])
        for s, (kind, u, v) in chunks.items():
            ok, gen = out[f"t{s}"]
            ok_t, gen_t = tiered[s]._apply_ops(kind, u, v)
            assert np.array_equal(ok, np.asarray(ok_t))
            assert np.array_equal(ok, oracle_apply(oracles[s], kind, u, v))
            assert gen == gen_t
            st = eng.tenant_state(f"t{s}")
            lab = np.asarray(st.ccid)
            assert np.array_equal(lab, np.asarray(tiered[s].state.ccid))
            assert lab.tolist() == tarjan_ccid(NV, oracles[s].edges,
                                               oracles[s].alive)
            assert int(st.n_ccs) == int(tiered[s].state.n_ccs)
    tiers = eng.stats()["lane_tier_steps"]
    assert tiers["dense"] > 0
    assert tiers["compact"] == tiers["full"] == 0


def _whiles(jaxpr, acc):
    for e in jaxpr.eqns:
        if e.primitive.name == "while":
            acc.append(e)
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    _whiles(sub.jaxpr, acc)
                elif hasattr(sub, "eqns"):
                    _whiles(sub, acc)
    return acc


@pytest.mark.parametrize("dense_capacity,sparse", [(NV, False),
                                                   (NV // 2, True),
                                                   (0, True)])
def test_dense_lane_step_has_no_sparse_fixpoint(dense_capacity, sparse):
    """The sparse tiers' fixpoints are the step's only ``while`` loops
    (the edge table's probe loops have static trip counts): a whole-lane
    dense config traces none, and any smaller dense block keeps them."""
    cfg = cfg_for(dense_capacity)
    lanes = jnp.zeros((16,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda s, o: dynamic._apply_batch_impl(s, o, cfg))(
            gs.empty(cfg), dynamic.OpBatch(lanes, lanes, lanes))
    assert bool(_whiles(jaxpr.jaxpr, [])) == sparse


@pytest.mark.parametrize("n", [1, 3, 6])
def test_create_tenants_equals_create_tenant(n, monkeypatch):
    cfg = cfg_for(NV)
    states = [built(cfg, 100 + i) for i in range(n)]
    calls = []
    orig = engine_mod._append_lanes

    def counted(stack, lanes):
        calls.append(len(lanes))
        return orig(stack, lanes)

    monkeypatch.setattr(engine_mod, "_append_lanes", counted)
    bulk, one = TenantEngine(**KNOBS), TenantEngine(**KNOBS)
    bulk.create_tenant("first", cfg, state=built(cfg, 99))
    one.create_tenant("first", cfg, state=built(cfg, 99))
    seen = len(telemetry.records()[0])
    bulk.create_tenants([(f"t{i}", cfg, st) for i, st in enumerate(states)])
    assert calls == [n]                 # one stacking program for n lanes
    prov = [r for r in telemetry.records()[0][seen:]
            if r.name == "engine.provision"]
    assert [r.attrs["tenants"] for r in prov] == [n]
    for i, st in enumerate(states):
        one.create_tenant(f"t{i}", cfg, state=st)
    assert bulk.stats()["provisioned"] == n
    assert one.stats()["provisioned"] == 0
    assert bulk.tenant_ids() == one.tenant_ids()
    for tid in one.tenant_ids():
        (sb, cb, gb), (so, co, go) = bulk.pin(tid), one.pin(tid)
        assert (cb, gb) == (co, go)
        assert gb == 0
        for a, b in zip(jax.tree.leaves(sb), jax.tree.leaves(so)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    # the provisioned stacks step like the appended ones
    rng = np.random.default_rng(n)
    chunks = [(tid, *op_chunk(rng, 16)) for tid in one.tenant_ids()]
    out_b, out_o = bulk.apply_chunks(chunks), one.apply_chunks(chunks)
    for tid, _, _, _ in chunks:
        assert np.array_equal(out_b[tid][0], out_o[tid][0])
        assert out_b[tid][1] == out_o[tid][1]
        assert np.array_equal(np.asarray(bulk.tenant_state(tid).ccid),
                              np.asarray(one.tenant_state(tid).ccid))


def test_service_create_tenants_serves_built_graphs():
    cfg = cfg_for(NV)
    svc = MultiTenantService(cfg, tenant_batches=(1, 2, 4), **KNOBS)
    try:
        tids = svc.create_tenants([built(cfg, 7), built(cfg, 8)])
        assert tids == ["t0", "t1"]
        assert svc.create_tenant() == "t2"
        for tid, seed in zip(tids, (7, 8)):
            sess = svc.session(tid)
            assert sess.gen == 0
            want = oracle_for(seed).ccid()
            assert np.asarray(sess.state.ccid).tolist() == want
        assert svc.stats()["engine"]["provisioned"] == 2
    finally:
        svc.close()
