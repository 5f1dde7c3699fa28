"""Chip smoke test: the SCC service's main paths on one TPU, checked exactly.

    python chip_smoke.py

Run from the root of a checkout on a machine with a TPU.  It drives the
public serving path once at a size users would call real, and checks every
answer against an independent host reference:

* Phase A, one large graph (``update_1m`` of ``configs/smscc.py``: 2^20
  vertex slots, 2^23 edge slots, 8192-op chunks).  A seeded random digraph
  of 4 * 2^20 edge draws (out-degree 4, half the table; the scale of SNAP
  web-Google) is bulk-loaded and handed to ``SCCService``.  Rounds of four
  chunks go through one ``GraphClient``: chunks built so that the repair
  gate skips, the compact tier repairs (the native ``frontier_expand``),
  and the full tier repairs; the last is a ``launch/workload.py`` mix.
  ``SameSCC``, ``CommunityOf`` and ``Reachable`` queries go through a
  ``QueryBroker`` whose buckets keep a ``Reachable`` flush inside HBM.
* Phase B, many small tenants (the ``serve --tenants`` path): 32 tenant
  graphs of 2^10 vertices and 2^12 edge slots behind one
  ``MultiTenantService``, one client thread each, so both sparse kernels
  run natively under ``vmap``.

The reference is numpy and scipy only: a host replay of the op semantics
(per-op acks and the live edge set), ``connected_components(
connection="strong")`` with labels mapped to the minimum member id, and a
breadth-first search per ``Reachable`` source.  Any mismatch exits
non-zero.  So does a host where JAX finds no TPU: nothing runs on the CPU.
The last line of standard output, on success only, is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

# op kinds of repro.core.dynamic, restated so the reference owes nothing
# to the code under test
ADD_EDGE, REM_EDGE, ADD_VERTEX, REM_VERTEX = 0, 1, 2, 3

PHASE_A_ROUNDS = 2
PHASE_A_QUERY_BUCKET = 16     # Reachable at 2^20 vertices: ~5.4 GB temp
TENANTS = 32
TENANT_NV = 1 << 10
TENANT_CAP = 1 << 12
TENANT_WAVES = 3


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


# ----------------------------------------------------------- reference ---


def _first_lanes(cand: np.ndarray, key: np.ndarray) -> np.ndarray:
    """The lowest candidate lane of every distinct key wins."""
    idx = np.nonzero(cand)[0]
    _, first = np.unique(key[idx], return_index=True)
    win = np.zeros(cand.shape[0], bool)
    win[idx[first]] = True
    return win


class HostGraph:
    """Sequential reference of the update semantics: alive vertex slots
    and the live edge set as sorted ``u * nv + v`` keys.  A batch
    linearizes RemoveVertex, RemoveEdge, AddVertex, AddEdge, ties going to
    the lowest lane; a vertex removal drops its incident edges."""

    def __init__(self, nv: int, alive: np.ndarray, keys: np.ndarray):
        self.nv = nv
        self.alive = alive.copy()
        self.keys = np.unique(keys.astype(np.int64))

    def has(self, key: np.ndarray) -> np.ndarray:
        if self.keys.size == 0:
            return np.zeros(key.shape, bool)
        i = np.minimum(np.searchsorted(self.keys, key), self.keys.size - 1)
        return self.keys[i] == key

    def apply(self, kind, u, v) -> np.ndarray:
        nv = self.nv
        kind = np.asarray(kind)
        u = np.asarray(u, np.int64)
        v = np.asarray(v, np.int64)
        edge_op = (kind == ADD_EDGE) | (kind == REM_EDGE)
        in_range = (u >= 0) & (u < nv) & np.where(
            edge_op, (v >= 0) & (v < nv), True)
        uc = np.clip(u, 0, nv - 1)
        vc = np.clip(v, 0, nv - 1)
        key = uc * nv + vc
        ok = np.zeros(kind.shape[0], bool)

        win = _first_lanes((kind == REM_VERTEX) & in_range
                           & self.alive[uc], uc)
        ok |= win
        killed = np.zeros(nv, bool)
        killed[uc[win]] = True
        self.alive &= ~killed
        self.keys = self.keys[~(killed[self.keys // nv]
                                | killed[self.keys % nv])]

        ends = self.alive[uc] & self.alive[vc]
        win = _first_lanes((kind == REM_EDGE) & in_range & ends
                           & self.has(key), key)
        ok |= win
        self.keys = self.keys[~np.isin(self.keys, key[win])]

        win = _first_lanes((kind == ADD_VERTEX) & in_range
                           & ~self.alive[uc], uc)
        ok |= win
        self.alive[uc[win]] = True

        ends = self.alive[uc] & self.alive[vc]
        win = _first_lanes((kind == ADD_EDGE) & in_range & ends
                           & ~self.has(key), key)
        ok |= win
        self.keys = np.union1d(self.keys, key[win])
        return ok

    def csr(self):
        from scipy.sparse import csr_matrix
        s, d = self.keys // self.nv, self.keys % self.nv
        return csr_matrix((np.ones(s.size, np.int8), (s, d)),
                          shape=(self.nv, self.nv))

    def labels(self) -> np.ndarray:
        """Strong components, each labelled by its minimum member id; the
        sentinel ``nv`` for dead slots."""
        from scipy.sparse.csgraph import connected_components
        _, comp = connected_components(self.csr(), directed=True,
                                       connection="strong")
        order = np.argsort(comp, kind="stable")
        starts = np.r_[0, np.nonzero(np.diff(comp[order]))[0] + 1]
        min_id = np.empty(comp.max() + 1, np.int64)
        min_id[comp[order[starts]]] = order[starts]
        lab = min_id[comp]
        lab[~self.alive] = self.nv
        return lab

    def reachable(self, us, vs) -> np.ndarray:
        from scipy.sparse.csgraph import breadth_first_order
        g = self.csr()
        out = np.zeros(len(us), bool)
        seen = {}
        for i, (a, b) in enumerate(zip(us, vs)):
            if not (self.alive[a] and self.alive[b]):
                continue
            if a not in seen:
                mask = np.zeros(self.nv, bool)
                mask[breadth_first_order(g, a, directed=True,
                                         return_predecessors=False)] = True
                seen[a] = mask
            out[i] = seen[a][b]
        return out


def check_state(state, host: HostGraph, where: str) -> np.ndarray:
    """Device partition, alive mask and live edge set equal the host's."""
    import jax
    v_alive, ccid, src, dst, st, n_ccs, ovf = jax.device_get(
        (state.v_alive, state.ccid, state.edges.src, state.edges.dst,
         state.edges.state, state.n_ccs, state.overflow))
    check(int(ovf) == 0, f"{where}: edge table overflowed ({int(ovf)})")
    check(np.array_equal(v_alive, host.alive), f"{where}: alive masks differ")
    live = st == 1
    keys = np.sort(src[live].astype(np.int64) * host.nv + dst[live])
    check(np.array_equal(keys, host.keys),
          f"{where}: live edge sets differ ({keys.size} vs "
          f"{host.keys.size})")
    ref = host.labels()
    bad = np.nonzero(ccid != ref)[0]
    check(bad.size == 0,
          f"{where}: {bad.size} SCC labels differ from scipy, e.g. vertex "
          f"{bad[:3].tolist()}: {ccid[bad[:3]].tolist()} vs "
          f"{ref[bad[:3]].tolist()}")
    reps = int(np.sum(host.alive & (ref == np.arange(host.nv))))
    check(int(n_ccs) == reps, f"{where}: n_ccs {int(n_ccs)} vs {reps}")
    return ref


# -------------------------------------------------------------- phase A ---


def _giant_pairs(rng, ref, alive, n):
    """``n`` (u, v) pairs inside the largest SCC: AddEdge there changes no
    component, so the repair gate skips the step."""
    lab = ref[alive]
    vals, counts = np.unique(lab, return_counts=True)
    members = np.nonzero(alive & (ref == vals[np.argmax(counts)]))[0]
    return rng.choice(members, n), rng.choice(members, n)


def skip_chunk(rng, host, n):
    """AddEdge inside the giant SCC plus RemoveEdge of edges between two
    SCCs: no straddling insert and no class hit by a deletion."""
    ref = host.labels()
    n_rem = n // 4
    u, v = _giant_pairs(rng, ref, host.alive, n - n_rem)
    s, d = host.keys // host.nv, host.keys % host.nv
    cross = np.nonzero(ref[s] != ref[d])[0]
    pick = rng.choice(cross, min(n_rem, cross.size), replace=False)
    kind = np.r_[np.full(u.size, ADD_EDGE), np.full(pick.size, REM_EDGE)]
    u = np.r_[u, s[pick]]
    v = np.r_[v, d[pick]]
    perm = rng.permutation(kind.size)
    return kind[perm], u[perm], v[perm]


def compact_chunk(rng, host, n, max_pairs):
    """2-cycles between vertices with no in-edges: each pair merges into
    one small SCC, and nothing else reaches the pair, so the repair
    region is the pairs alone and fits the compact tier.  The rest of the
    chunk is giant-internal AddEdge."""
    indeg = np.bincount(host.keys % host.nv, minlength=host.nv)
    pool = np.nonzero(host.alive & (indeg == 0))[0]
    p = min(pool.size // 2, max_pairs, n // 2)
    check(p > 0, "compact chunk: no vertex without in-edges to pair")
    a, b = np.split(rng.choice(pool, 2 * p, replace=False), 2)
    fu, fv = _giant_pairs(rng, host.labels(), host.alive, n - 2 * p)
    kind = np.full(n, ADD_EDGE)
    return kind, np.r_[a, b, fu], np.r_[b, a, fv]


def workload_chunk(nv, n, step, seed):
    """The paper's mixed Add/Remove (V+E) batch from launch/workload.py;
    its vertex removals hit the giant SCC, which sends repair to the full
    tier."""
    from repro.launch import workload
    ops = workload.op_stream(nv, n, step=step, add_frac=0.5, seed=seed)
    return tuple(np.asarray(x) for x in (ops.kind, ops.u, ops.v))


def run_queries(client, host, rng, n_pair, n_reach, gen):
    """SameSCC, CommunityOf and Reachable through the broker, each answer
    against the host reference at the committed generation."""
    from repro.api import CommunityOf, Reachable, SameSCC
    nv = host.nv
    ref = host.labels()
    half = n_pair // 2
    su = rng.integers(0, nv, n_pair)
    # half random pairs, half (u, the min member of u's SCC): the latter
    # answer True whenever u is alive
    sv = np.r_[rng.integers(0, nv, half), np.minimum(ref[su[half:]], nv - 1)]
    cu = rng.integers(0, nv, n_pair)
    outdeg = np.bincount(host.keys // nv, minlength=nv)
    # half the sources have no out-edge: False unless the target is u
    sinks = np.nonzero(host.alive & (outdeg == 0))[0]
    if sinks.size == 0:
        sinks = np.arange(nv)
    ru = np.r_[rng.integers(0, nv, n_reach - n_reach // 2),
               rng.choice(sinks, n_reach // 2)]
    rv = rng.integers(0, nv, n_reach)
    ops = ([SameSCC(int(a), int(b)) for a, b in zip(su, sv)]
           + [CommunityOf(int(a)) for a in cu]
           + [Reachable(int(a), int(b)) for a, b in zip(ru, rv)])
    res = client.submit_many(ops)
    check(all(r.gen == gen for r in res),
          f"queries answered at gens {sorted({r.gen for r in res})}, "
          f"committed {gen}")
    got = [r.value for r in res]
    want_same = host.alive[su] & host.alive[sv] & (ref[su] == ref[sv])
    check(got[:n_pair] == want_same.tolist(), "SameSCC answers differ")
    check(got[n_pair:2 * n_pair] == ref[cu].tolist(),
          "CommunityOf answers differ")
    check(got[2 * n_pair:] == host.reachable(ru, rv).tolist(),
          "Reachable answers differ from the scipy BFS")
    return len(ops), int(np.sum(got[2 * n_pair:]))


def resolved_impls(cfg) -> dict:
    """What each sparse kernel hook resolves to at the widths it runs at:
    the full-table sweeps at ``n_vertices``, the compact repair tier at
    ``region_vertex_capacity``, the probes at ``edge_capacity``."""
    from repro.kernels.frontier_expand import ops as frontier_ops
    from repro.kernels.hash_probe import ops as hash_probe_ops
    impl = cfg.sparse_impl
    return {"frontier_expand@nv": frontier_ops.resolve_impl(
                impl, cfg.n_vertices),
            "frontier_expand@vcap": frontier_ops.resolve_impl(
                impl, cfg.region_vertex_capacity),
            "hash_probe@cap": hash_probe_ops.resolve_impl(
                impl, cfg.edge_capacity)}


def lowered_kernel_calls(lowered) -> int:
    """Mosaic kernels in a lowered program (0 when none runs natively)."""
    return lowered.as_text().count("tpu_custom_call")


def phase_a(*, nv, cap, n_edges, chunk, rounds, query_bucket, n_pair,
            n_reach, sparse_impl="auto", seed=0):
    import jax
    import jax.numpy as jnp

    from repro.api import GraphClient, updates_from_arrays
    from repro.configs import smscc
    from repro.core import dynamic, graph_state as gs
    from repro.core.broker import QueryBroker
    from repro.core.service import SCCService

    cfg = smscc.config(n_vertices=nv, edge_capacity=cap,
                       sparse_impl=sparse_impl)
    vcap = cfg.region_vertex_capacity
    impls = resolved_impls(cfg)
    log(f"phase A: nv={nv} cap={cap} vcap={vcap} chunk={chunk} "
        f"kernels {impls}")
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nv, n_edges).astype(np.int32)
    dst = rng.integers(0, nv, n_edges).astype(np.int32)

    t0 = time.perf_counter()
    state = jax.jit(gs.from_arrays, static_argnums=0)(
        cfg, jnp.asarray(src), jnp.asarray(dst))
    state = dynamic.recompute(state, cfg)
    jax.block_until_ready(state.ccid)
    load_s = time.perf_counter() - t0
    host = HostGraph(nv, np.ones(nv, bool),
                     src.astype(np.int64) * nv + dst)
    check_state(state, host, "load")
    log(f"phase A: loaded {host.keys.size} edges ({n_edges} draws) in "
        f"{load_s:.3f} s, partition equals scipy")

    svc = SCCService(cfg, buckets=(chunk,), state=state,
                     scan_lengths=smscc.SCAN_LENGTHS)
    k = 4
    lanes = jax.ShapeDtypeStruct((k, chunk), jnp.int32)
    n_calls = lowered_kernel_calls(dynamic.apply_batch_scan.lower(
        state, dynamic.OpBatch(lanes, lanes, lanes), cfg))
    log(f"phase A: Mosaic kernels in the K={k} x B={chunk} step program: "
        f"{n_calls}")
    if impls["frontier_expand@vcap"] == "pallas":
        check(n_calls > 0, "compact tier resolved to pallas, but the step "
              "program holds no Mosaic kernel")

    broker = QueryBroker(svc, buckets=(query_bucket,)).start()
    client = GraphClient(svc, broker)
    n_ops = n_queries = 0
    try:
        q, _ = run_queries(client, host, rng, n_pair, n_reach, svc.gen)
        n_queries += q
        for r in range(rounds):
            tiers0 = dict(svc.repair_tier_steps)
            parts, expect = [], []
            for build in (
                    lambda: skip_chunk(rng, host, chunk),
                    lambda: compact_chunk(rng, host, chunk, chunk // 4),
                    lambda: workload_chunk(nv, chunk, r, seed),
                    lambda: skip_chunk(rng, host, chunk)):
                kind, u, v = build()
                expect.append(host.apply(kind, u, v))
                parts.append((kind, u, v))
            kind, u, v = (np.concatenate(x) for x in zip(*parts))
            ops = updates_from_arrays(kind, u, v)
            t0 = time.perf_counter()
            res = client.submit_many(ops)
            step_s = time.perf_counter() - t0
            got = np.array([x.value for x in res])
            want = np.concatenate(expect)
            check(np.array_equal(got, want),
                  f"round {r}: {int(np.sum(got != want))} acks differ from "
                  f"the host replay")
            tiers = {t: svc.repair_tier_steps[t] - tiers0[t]
                     for t in tiers0}
            check(tiers == {"dense": 0, "compact": 1, "full": 1,
                            "skipped": 2},
                  f"round {r}: repair tiers {tiers}, expected skip, "
                  f"compact, full, skip")
            check_state(svc.state, host, f"round {r}")
            n_ops += len(ops)
            log(f"phase A round {r}: {len(ops)} ops in {step_s:.3f} s, "
                f"{int(want.sum())} accepted, tiers {tiers}, partition "
                f"and edges equal the host")
            t0 = time.perf_counter()
            q, n_true = run_queries(client, host, rng, n_pair, n_reach,
                                    svc.gen)
            n_queries += q
            log(f"phase A round {r}: {q} queries in "
                f"{time.perf_counter() - t0:.3f} s equal the host "
                f"({n_true}/{n_reach} reachable)")
    finally:
        broker.stop()
    stats = client.stats()
    log(f"phase A: ops={n_ops} queries={n_queries} "
        f"repair_tier_steps={svc.repair_tier_steps} "
        f"scan_dispatches={stats['scan_dispatches']} "
        f"broker_flushes={stats['flushes']}")
    return {"ops": n_ops, "queries": n_queries, "impls": impls,
            "kernel_calls": n_calls,
            "tiers": dict(svc.repair_tier_steps)}


# -------------------------------------------------------------- phase B ---


def phase_b(*, tenants, nv, cap, waves, sparse_impl="auto", seed=0):
    import jax

    from repro.api import CommunityOf, SameSCC, updates_from_arrays
    from repro.configs import smscc
    from repro.core import dynamic, graph_state as gs
    from repro.launch import workload
    from repro.tenancy import MultiTenantService, engine

    chunk = nv  # wave 0 adds every vertex slot in one chunk
    cfg = smscc.config(n_vertices=nv, edge_capacity=cap,
                       sparse_impl=sparse_impl)
    vcap = cfg.region_vertex_capacity
    impls = resolved_impls(cfg)
    states = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((tenants,) + s.shape, s.dtype),
        jax.eval_shape(lambda: gs.empty(cfg)))
    lanes = jax.ShapeDtypeStruct((tenants, 1, chunk), jax.numpy.int32)
    n_calls = lowered_kernel_calls(engine._vmapped_scan.lower(
        states, dynamic.OpBatch(lanes, lanes, lanes), cfg))
    log(f"phase B: {tenants} tenants nv={nv} cap={cap} vcap={vcap} "
        f"chunk={chunk} kernels {impls}, Mosaic kernels in the vmapped "
        f"step program: {n_calls}")
    if "pallas" in (impls["frontier_expand@nv"], impls["hash_probe@cap"]):
        check(n_calls > 0, "tenant kernels resolved to pallas, but the "
              "vmapped step holds no Mosaic kernel")

    mts = MultiTenantService(
        cfg, buckets=(chunk,), scan_lengths=smscc.SCAN_LENGTHS,
        tenant_batches=(tenants,), max_pending_ops=tenants * chunk,
        coalesce_ops=tenants * chunk, flush_deadline_s=1.0)
    tids = [mts.create_tenant() for _ in range(tenants)]
    hosts = [HostGraph(nv, np.zeros(nv, bool), np.zeros(0, np.int64))
             for _ in tids]
    errors: list = []

    def drive(i, tid):
        try:
            client = mts.client(tid)
            for w in range(waves + 1):
                if w == 0:
                    kind = np.full(nv, ADD_VERTEX)
                    u = np.arange(nv)
                    v = np.zeros(nv, np.int64)
                else:
                    ops = workload.op_stream(nv, chunk, step=w,
                                             add_frac=0.8,
                                             seed=seed * 1000 + i)
                    kind, u, v = (np.asarray(x)
                                  for x in (ops.kind, ops.u, ops.v))
                want = hosts[i].apply(kind, u, v)
                res = client.submit_many(updates_from_arrays(kind, u, v))
                got = np.array([r.value for r in res])
                check(np.array_equal(got, want),
                      f"tenant {tid} wave {w}: "
                      f"{int(np.sum(got != want))} acks differ")
            client.close()
        except BaseException as e:  # surfaced by the main thread
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=drive, args=(i, tid))
               for i, tid in enumerate(tids)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    rng = np.random.default_rng(seed + 1)
    n_queries = 0
    for i, tid in enumerate(tids):
        ref = check_state(mts.session(tid).state, hosts[i], f"tenant {tid}")
        client = mts.client(tid)
        su, sv, cu = (rng.integers(0, nv, 32) for _ in range(3))
        res = client.submit_many([SameSCC(int(a), int(b))
                                  for a, b in zip(su, sv)]
                                 + [CommunityOf(int(a)) for a in cu])
        client.close()
        got = [r.value for r in res]
        want = (hosts[i].alive[su] & hosts[i].alive[sv]
                & (ref[su] == ref[sv])).tolist() + ref[cu].tolist()
        check(got == want, f"tenant {tid}: query answers differ")
        n_queries += len(res)
    agg = mts.stats()
    mts.close()
    n_ops = tenants * (waves + 1) * chunk
    log(f"phase B: {n_ops} ops over {tenants} tenants x {waves + 1} waves "
        f"in {wall:.3f} s, {n_queries} queries; every tenant's partition "
        f"equals scipy; queue waves={agg['queue']['waves']} "
        f"engine compile_count={agg['engine']['compile_count']} "
        f"solo_replays={agg['engine']['solo_replays']}")
    return {"ops": n_ops, "queries": n_queries, "impls": impls,
            "kernel_calls": n_calls}


# ----------------------------------------------------------------- main ---


def _tpu_or_exit():
    try:
        import jax
        devices = jax.devices()
    except Exception as e:  # no backend at all
        sys.exit(f"chip_smoke: JAX found no device ({e}); nothing was run")
    d = devices[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {d.platform!r}); "
                 f"this smoke runs only on a TPU, nothing was run")
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    return d, len(devices)


def _compile_clock():
    """Seconds spent in XLA compiles (or persistent-cache reads)."""
    import jax
    total = {"n": 0, "s": 0.0}

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total["n"] += 1
            total["s"] += duration
    jax.monitoring.register_event_duration_secs_listener(listen)
    return total


def main():
    device, count = _tpu_or_exit()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.configs import smscc
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    compiles = _compile_clock()
    t0 = time.perf_counter()
    shape = smscc.SHAPES["update_1m"]
    a = phase_a(nv=shape["n_vertices"], cap=shape["edge_capacity"],
                n_edges=4 * shape["n_vertices"], chunk=shape["batch"],
                rounds=PHASE_A_ROUNDS, query_bucket=PHASE_A_QUERY_BUCKET,
                n_pair=96, n_reach=32)
    check(a["impls"]["frontier_expand@vcap"] == "pallas",
          "the compact tier did not resolve to the native frontier kernel")
    b = phase_b(tenants=TENANTS, nv=TENANT_NV, cap=TENANT_CAP,
                waves=TENANT_WAVES)
    check(b["impls"]["frontier_expand@nv"] == "pallas"
          and b["impls"]["hash_probe@cap"] == "pallas",
          "the tenant sweeps did not resolve to the native kernels")
    stats = device.memory_stats() or {}
    log(f"total: {a['ops'] + b['ops']} ops, {a['queries'] + b['queries']} "
        f"queries in {time.perf_counter() - t0:.3f} s; compiles="
        f"{compiles['n']} compile_s={compiles['s']:.3f}; "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
