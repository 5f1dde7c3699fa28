"""Many tenant graphs, each built loaded, provisioned in one step.

Each tenant's seeded R-MAT graph is bulk-loaded and labelled on the
device as ``durable_service`` loads its one graph (``gs.from_arrays``,
then the full static SCC), and ``MultiTenantService.create_tenants``
stacks them all with one program: every tenant boots as its loaded graph
at generation 0, and the warm-up's load wave is empty.  Sessions,
waves, warm-up, counters and final states are those of ``multi_tenant``,
but for the tombstone compaction the warm-up forces.
"""
from __future__ import annotations

import numpy as np

from bench import generators as gen
from bench import workload
from bench.stacks import multi_tenant


class Stack(multi_tenant.Stack):
    def __init__(self, config: dict, seed: int, workdir: str,
                 sessions: int):
        import jax
        import jax.numpy as jnp

        from repro.api import GraphClient
        from repro.configs import smscc
        from repro.core import dynamic, graph_state as gs
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
        # a program without bulk provisioning stops here, before building
        provision = self.service.create_tenants
        load = jax.jit(gs.from_arrays, static_argnums=(0, 3))
        states, self.graphs, self.boot = [], [], []
        for i in range(config["tenants"]):
            src, dst = gen.rmat_edges(seed, nu, config["edges"],
                                      g["abcd"], stream=i)
            st = dynamic.recompute(
                load(self.cfg, jnp.asarray(src), jnp.asarray(dst), nu),
                self.cfg)
            states.append(st._replace(gen=jnp.zeros_like(st.gen)))
            self.graphs.append(workload.GraphShape(
                nv, g["abcd"], gen.vertex_perm(seed, nu, i), src, dst))
            self.boot.append((np.arange(nv) < nu,
                              src.astype(np.int64) * nv + dst, 0))
        dropped = sum(jax.device_get([st.overflow for st in states]))
        if dropped:
            raise RuntimeError(f"the bulk load dropped {dropped} edges")
        self.tids = provision(states)
        del states
        self.load = []                  # the tenants boot loaded
        self._clients = [[GraphClient(self.service.session(tid))
                          for tid in self.tids] for _ in range(sessions)]
        self._sessions = sessions

    def _warm_compaction(self, record, n_ops: int):
        """Remove vertices of the last tenant, each taking its incident
        edges to tombstones, until the tenant compacts once; then add the
        vertices back (their edges stay gone).  A dense graph fills its
        tombstones in a few waves this way; should it run out of vertices
        first, the insert-and-delete warm-up of ``multi_tenant`` takes
        over."""
        g = len(self.tids) - 1
        tid = self.tids[g]
        removed = []
        for start in range(0, self._alive, n_ops):
            if self.service.engine.tenant_telemetry(tid)["compactions"]:
                break
            ids = np.arange(start, min(start + n_ops, self._alive),
                            dtype=np.int32)
            removed.append(ids)
            self.flush_wave(record, [
                (g, np.full(ids.size, gen.REM_VERTEX, np.int32), ids,
                 np.zeros(ids.size, np.int32))])
        for ids in removed:
            self.flush_wave(record, [
                (g, np.full(ids.size, gen.ADD_VERTEX, np.int32), ids,
                 np.zeros(ids.size, np.int32))])
        super()._warm_compaction(record, n_ops)
