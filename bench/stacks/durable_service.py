"""One large graph behind ``DurableService`` with ``GraphClient`` sessions.

The graph is generated on the host from the seed, bulk-loaded in one
jitted call, labelled by the full static SCC, and handed to a durable
writer that fsyncs its WAL before every apply.  Each session gets its own
``GraphClient``.

Durability is read from outside the writer: every ``os.fsync`` of a file
in the store's WAL directory is timed while the service runs, and once
it is closed the store is opened cold, as a restart would, and its state
read back.
"""
from __future__ import annotations

import os
import time

import numpy as np

from bench import generators as gen
from bench import workload


class Stack:
    def __init__(self, config: dict, seed: int, workdir: str,
                 sessions: int):
        import jax
        import jax.numpy as jnp

        from repro.api import GraphClient
        from repro.ckpt import durable
        from repro.configs import smscc
        from repro.core import dynamic, graph_state as gs

        g = config["graph"]
        nv = config["vertex_slots"]
        n = config["vertices"]
        src, dst = gen.rmat_edges(seed, n, config["edges"], g["abcd"])
        self.cfg = smscc.config(n_vertices=nv,
                                edge_capacity=config["edge_slots"],
                                **config["engine"])
        # slots 0..n-1 alive at boot, the rest free for AddVertex
        state = jax.jit(gs.from_arrays, static_argnums=(0, 3))(
            self.cfg, jnp.asarray(src), jnp.asarray(dst), n)
        state = dynamic.recompute(state, self.cfg)
        jax.block_until_ready(state.ccid)
        svc = config["service"]
        self._store = os.path.join(workdir, "store")
        self._service_kw = dict(buckets=tuple(svc["buckets"]),
                                scan_lengths=smscc.SCAN_LENGTHS)
        self.service = durable.DurableService(
            self.cfg, self._store, state=state,
            sync_every=svc["sync_every"],
            snapshot_every=svc["snapshot_every"], **self._service_kw)
        self._clients = [GraphClient(self.service) for _ in range(sessions)]
        self.graphs = [workload.GraphShape(
            nv, g["abcd"], gen.vertex_perm(seed, n), src, dst)]
        # (alive, live keys, generation) of each graph at boot
        self.boot = [(np.arange(nv) < n, src.astype(np.int64) * nv + dst,
                      int(state.gen))]
        self.wal_fsyncs = []
        self._watch_fsync(os.path.realpath(durable.wal_dir(self._store)))

    def _watch_fsync(self, wal: str):
        """Time the return of every fsync of a WAL file from here until
        the service closes (boot's own fsyncs are done by now)."""
        real, times = os.fsync, self.wal_fsyncs

        def fsync(fd):
            real(fd)
            try:
                path = os.readlink(f"/proc/self/fd/{int(fd)}")
            except (OSError, TypeError, ValueError):
                return
            if path.startswith(wal + os.sep):
                times.append(time.perf_counter())

        os.fsync = fsync
        self._unwatch = lambda: setattr(os, "fsync", real)

    def client(self, session: int, graph: int):
        return self._clients[session]

    def warm(self, issue, warm_pool: list, record):
        """One request of each kind the traffic sends compiles every
        program the window runs: the donated K=1 step at the one bucket,
        the double-buffer copy and the tombstone probe."""
        done = set()
        for req in warm_pool:
            if req.kind not in done:
                issue(0, req)
                done.add(req.kind)

    def counters(self) -> dict:
        s = self.service.stats()
        return {"steps": {k: s[f"repair_{k}_steps"]
                          for k in ("dense", "compact", "full", "skipped")},
                "scan_dispatches": s["scan_dispatches"],
                "fallback_chunks": s["fallback_chunks"],
                "grows": s["grows"], "compactions": s["compactions"]}

    def final_states(self) -> list:
        import jax
        st = self.service.state
        alive, ccid, src, dst, est, n_ccs = jax.device_get(
            (st.v_alive, st.ccid, st.edges.src, st.edges.dst,
             st.edges.state, st.n_ccs))
        live = est == 1
        keys = src[live].astype(np.int64) * self.cfg.n_vertices + dst[live]
        return [(alive, ccid, keys, int(n_ccs))]

    def close(self):
        for c in self._clients:
            c.close()
        self.service.close()
        self._unwatch()
        self.service = None
        self._clients = []

    def reopen(self) -> dict:
        """Open the closed store cold, as a restart would (newest snapshot
        and the WAL's replay), and read back its state and generation;
        with the WAL fsyncs timed while the writer ran."""
        import jax

        from repro.ckpt import durable
        svc = durable.DurableService.open(self._store, self.cfg,
                                          **self._service_kw)
        try:
            st = svc.state
            alive, ccid, src, dst, est, n_ccs = jax.device_get(
                (st.v_alive, st.ccid, st.edges.src, st.edges.dst,
                 st.edges.state, st.n_ccs))
            gen_ = int(svc.gen)
        finally:
            svc.close()
        live = est == 1
        keys = src[live].astype(np.int64) * self.cfg.n_vertices + dst[live]
        return {"states": [(alive, ccid, keys, int(n_ccs))], "gens": [gen_],
                "wal_fsyncs": list(self.wal_fsyncs)}
