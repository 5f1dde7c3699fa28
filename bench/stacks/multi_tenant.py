"""Many small tenant graphs behind one ``MultiTenantService``.

Every tenant is created empty, then loaded in one admission wave: its
``vertices`` alive (the rest of its slots stay free for AddVertex) and
its seeded R-MAT edges among them.  Each session holds one
``GraphClient`` per tenant, since a client is a session over one tenant.
"""
from __future__ import annotations

import numpy as np

from bench import generators as gen
from bench import workload


class Stack:
    def __init__(self, config: dict, seed: int, workdir: str,
                 sessions: int):
        from repro.api import GraphClient
        from repro.configs import smscc
        from repro.tenancy import MultiTenantService

        g = config["graph"]
        nv = config["vertex_slots"]
        nu = self._alive = config["vertices"]
        self.cfg = smscc.config(n_vertices=nv,
                                edge_capacity=config["edge_slots"],
                                **config["engine"])
        svc = config["service"]
        self.service = MultiTenantService(
            self.cfg, buckets=tuple(svc["buckets"]),
            scan_lengths=smscc.SCAN_LENGTHS,
            tenant_batches=tuple(svc["tenant_batches"]),
            max_pending_ops=svc["max_pending_ops"],
            coalesce_ops=svc["coalesce_ops"],
            flush_deadline_s=svc["flush_deadline_s"])
        n = config["tenants"]
        self.tids = [self.service.create_tenant() for _ in range(n)]
        self.graphs, self.boot, load = [], [], []
        for i, tid in enumerate(self.tids):
            src, dst = gen.rmat_edges(seed, nu, config["edges"],
                                      g["abcd"], stream=i)
            self.graphs.append(workload.GraphShape(
                nv, g["abcd"], gen.vertex_perm(seed, nu, i), src, dst))
            self.boot.append((np.zeros(nv, bool), np.zeros(0, np.int64), 0))
            kind = np.r_[np.full(nu, gen.ADD_VERTEX, np.int32),
                         np.full(src.size, gen.ADD_EDGE, np.int32)]
            u = np.r_[np.arange(nu, dtype=np.int32), src]
            v = np.r_[np.zeros(nu, np.int32), dst]
            load.append((tid, kind, u, v))
        self.load = load
        self._clients = [[GraphClient(self.service.session(tid))
                          for tid in self.tids] for _ in range(sessions)]
        self._sessions = sessions

    def client(self, session: int, graph: int):
        return self._clients[session][graph]

    def flush_wave(self, record, chunks: list):
        """Apply one admission wave of ``(graph, kind, u, v)`` chunks
        through the service's own flush, as the queue's leader does."""
        res = self.service._flush_wave(
            [(self.tids[g], k, u, v) for g, k, u, v in chunks])
        for g, k, u, v in chunks:
            out = res[self.tids[g]]
            if isinstance(out, Exception):
                raise out
            ok, gen_ = out
            record(workload.Request("update", g, ops=(k, u, v)),
                   np.asarray(ok), int(gen_))

    def warm(self, issue, warm_pool: list, record):
        """Load every tenant in one wave, then warm each wave width the
        window can form (one lane per session at most), one tenant past
        its tombstone compaction, and the read path."""
        self.flush_wave(record, [
            (i, k, u, v) for i, (_, k, u, v) in enumerate(self.load)])
        updates = [r for r in warm_pool if r.kind == "update"]
        reads = [r for r in warm_pool if r.kind == "read"]
        n = len(self.tids)
        if updates:
            it = iter(updates * (1 + self._sessions * self._sessions
                                 // max(1, len(updates))))
            for width in range(1, min(self._sessions, n) + 1):
                self.flush_wave(record, [
                    ((width + j) % n, *next(it).ops)
                    for j in range(width)])
            self._warm_compaction(record, updates[0].ops[0].shape[0])
        if reads:
            issue(0, reads[0])

    def _warm_compaction(self, record, n_ops: int):
        """Insert fresh edges into the last tenant and delete them again
        until its tombstones pass the compaction threshold once (a
        compaction the warm-up misses compiles in the window, where the
        run's compile count shows it)."""
        g = len(self.tids) - 1
        tid = self.tids[g]
        rng = gen.rng_for(0, 999)
        for _ in range(8 * self.cfg.edge_capacity // n_ops):
            if self.service.engine.tenant_telemetry(tid)["compactions"]:
                return
            u = rng.integers(0, self._alive, n_ops).astype(np.int32)
            v = rng.integers(0, self._alive, n_ops).astype(np.int32)
            for kind in (gen.ADD_EDGE, gen.REM_EDGE):
                self.flush_wave(record, [
                    (g, np.full(n_ops, kind, np.int32), u, v)])

    def counters(self) -> dict:
        s = self.service.stats()
        q, e = s["queue"], s["engine"]
        return {"waves": q["waves"], "lanes": q["submitted"],
                "rejects": q["rejects"], "flushes": e["flushes"],
                "solo_replays": e["solo_replays"],
                "compile_count": e["compile_count"]}

    def final_states(self) -> list:
        import jax
        out = []
        for tid in self.tids:
            st = self.service.session(tid).state
            alive, ccid, src, dst, est, n_ccs = jax.device_get(
                (st.v_alive, st.ccid, st.edges.src, st.edges.dst,
                 st.edges.state, st.n_ccs))
            live = est == 1
            keys = (src[live].astype(np.int64) * self.cfg.n_vertices
                    + dst[live])
            out.append((alive, ccid, keys, int(n_ccs)))
        return out

    def reopen(self):
        """Nothing to reopen: the tenants live in memory."""
        return None

    def close(self):
        for row in self._clients:
            for c in row:
                c.close()
        self.service.close()
        self.service = None
        self._clients = []
