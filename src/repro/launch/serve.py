"""Serving driver: batched LM decode, recsys scoring, or the paper's
streaming SCC service on the host mesh.

    python -m repro.launch.serve --arch gemma3-12b --smoke
    python -m repro.launch.serve --arch mind --smoke
    python -m repro.launch.serve --arch smscc --steps 64
    python -m repro.launch.serve --arch smscc --steps 64 --readers 2
    python -m repro.launch.serve --arch smscc --steps 20 --readers 2 \
        --replicas 2 --dir /tmp/scc-store
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs


def serve_lm(mod, steps: int):
    from repro.models import transformer as tf
    cfg = mod.smoke_config()
    params = tf.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    b, prompt_len, cache_len = 4, 12, 64
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, prompt_len)),
                       jnp.int32)
    cache, logits = tf.prefill(params, toks, cfg, cache_len=cache_len)
    decode = jax.jit(lambda p, c, t: tf.decode_step(p, c, t, cfg))
    out = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    t0 = time.perf_counter()
    for _ in range(steps):
        out.append(tok)
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    print(f"decoded {steps} tokens x batch {b} in {dt:.2f}s "
          f"({steps*b/dt:.0f} tok/s)")
    print("sample:", [int(t[0]) for t in out[:16]])


def serve_mind(mod, steps: int):
    model = mod.MODULE
    cfg = mod.smoke_config()
    params = model.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    b, c = 32, 512
    score = jax.jit(lambda p, batch: model.serve_score(p, batch, cfg))
    t0 = time.perf_counter()
    for step in range(steps):
        batch = {
            "behavior": jnp.asarray(
                rng.integers(-1, cfg.n_items, (b, cfg.seq_len)),
                jnp.int32),
            "profile": jnp.asarray(
                rng.integers(-1, cfg.profile_vocab, (b, cfg.profile_len)),
                jnp.int32),
            "candidates": jnp.asarray(
                rng.integers(0, cfg.n_items, (b, c)), jnp.int32),
        }
        s = score(params, batch)
    jax.block_until_ready(s)
    dt = time.perf_counter() - t0
    print(f"scored {steps} requests x batch {b} x {c} candidates in "
          f"{dt:.2f}s ({steps*b*c/dt:.0f} scores/s)")


def serve_smscc(mod, steps: int, nv: int = 2048, chunk: int = 256,
                readers: int = 0, replicas: int = 0,
                directory: str | None = None):
    """The paper's on-line mode: a typed GraphClient update stream +
    wait-free query batches over the committed snapshot, via the SCC
    service layer.  With ``readers > 0`` the queries move off the update
    thread into per-reader client sessions over one QueryBroker that
    overlaps the update pipeline.  With ``replicas > 0`` the store goes
    durable instead: a WAL-backed writer plus N read replicas tailing
    the log serve the readers' read-your-writes rounds
    (:func:`repro.launch.replica.run_replicated_stream`; requires
    ``directory`` for the durable store)."""
    from repro.core import graph_state as gs
    from repro.core.service import SCCService
    from repro.launch import stream

    if replicas > 0:
        from repro.launch.replica import run_replicated_stream
        if directory is None:
            raise SystemExit("--replicas needs --dir (durable store root)")
        rep = run_replicated_stream(
            directory, replicas=replicas, n_ops=steps * 32,
            readers=max(readers, 1))
        print(rep.pretty())
        return

    cfg = mod.config(n_vertices=nv, edge_capacity=max(1024, nv),
                     max_probes=64, max_outer=64, max_inner=128)
    # boot with every vertex slot live (singleton SCCs) so the update mix
    # lands immediately instead of bouncing off dead endpoints; serving
    # runs the full fused update engine (scan super-chunks + growth
    # rehashes ahead of chunks that cannot fit)
    svc = SCCService(cfg, buckets=(64, chunk),
                     state=gs.all_singletons(cfg),
                     scan_lengths=mod.SCAN_LENGTHS, proactive_grow=True)
    if readers > 0:
        rep = stream.run_concurrent_stream(
            svc, n_ops=steps * chunk, readers=readers, add_frac=0.7,
            chunk=chunk, n_queries=1024)
    else:
        rep = stream.run_stream(svc, n_ops=steps * chunk, add_frac=0.7,
                                query_frac=0.5, chunk=chunk,
                                n_queries=1024)
    print(rep.pretty())
    # the unified GraphClient.stats() telemetry (service + broker merged)
    tele = ("gen", "pipelined_chunks", "fallback_chunks", "compile_count",
            "grows", "compactions", "flushes", "served", "max_coalesced",
            "gen_waits", "coalescing", "client_updates", "client_queries")
    print("[client.stats] " + " | ".join(
        f"{k}={rep[k]}" for k in tele if k in rep))


def serve_tenants(mod, steps: int, tenants: int, nv: int = 256,
                  chunk: int = 64, directory: str | None = None):
    """Multi-tenant serving: N independent session graphs behind ONE
    vmapped engine and one admission queue
    (:class:`repro.tenancy.MultiTenantService`).  Each tenant runs its
    own typed ``GraphClient`` session on its own thread; concurrent
    submits coalesce into tenant-batched vmapped dispatches.  With
    ``directory`` the store is durable per tenant (snapshot + WAL) and
    idle tenants are evicted/rehydrated transparently."""
    import threading

    from repro.api import SameSCC
    from repro.launch import stream
    from repro.tenancy import MultiTenantService

    cfg = mod.config(n_vertices=nv, edge_capacity=max(256, nv),
                     max_probes=64, max_outer=64, max_inner=64)
    mts = MultiTenantService(cfg, buckets=(chunk,),
                             scan_lengths=mod.SCAN_LENGTHS,
                             directory=directory,
                             coalesce_ops=tenants * chunk,
                             flush_deadline_s=0.005)
    tids = [mts.create_tenant() for _ in range(tenants)]
    done = []

    def drive(tid, i):
        client = mts.client(tid)
        rng = np.random.default_rng(100 + i)
        n_ops = 0
        client.submit_many(stream.typed_op_stream(
            nv, chunk, step=0, add_frac=1.0, seed=i,
            include_vertex_ops=True))
        for step in range(steps):
            client.submit_many(stream.typed_op_stream(
                nv, chunk, step=step + 1, add_frac=0.7, seed=i))
            n_ops += chunk
            qs = [SameSCC(int(a), int(b)) for a, b in
                  zip(rng.integers(0, nv, 16), rng.integers(0, nv, 16))]
            client.submit_many(qs)
        client.close()
        done.append(n_ops)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=drive, args=(tid, i))
               for i, tid in enumerate(tids)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    total = sum(done)
    agg = mts.stats()
    print(f"served {tenants} tenants x {steps} chunks "
          f"({total} update ops) in {wall:.2f}s "
          f"({int(total / wall)} ops/s aggregate)")
    q = agg["queue"]
    print(f"[queue] waves={q['waves']} causes={q['flush_causes']} "
          f"depth_max={q['depth_max_ops']} rejects={q['rejects']} "
          f"pool={q['pool']}")
    e = agg["engine"]
    print(f"[engine] compile_count={e['compile_count']} "
          f"(bound {e['compile_bound']}) solo_replays={e['solo_replays']} "
          f"occupancy={e['occupancy']['frac']}")
    for tid in tids[:4]:
        print(f"[tenant {tid}] " + " | ".join(
            f"{k}={v}" for k, v in mts.tenant_stats(tid).items()
            if k in ("gen", "applied_chunks", "fallback_chunks", "grows")))
    mts.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--readers", type=int, default=0,
                    help="smscc only: concurrent reader threads (0 = "
                         "serial query interleaving)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="smscc only: serve reads from N WAL-tailing "
                         "replicas over a durable writer (needs --dir)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="smscc only: serve N independent tenant graphs "
                         "behind one vmapped engine + admission queue")
    ap.add_argument("--dir", dest="directory", default=None,
                    help="smscc only: durable store root for --replicas "
                         "/ per-tenant stores for --tenants")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mod = configs.get(args.arch)
    if mod.FAMILY == "lm":
        serve_lm(mod, args.steps)
    elif mod.FAMILY == "recsys":
        serve_mind(mod, args.steps)
    elif mod.FAMILY == "smscc":
        if args.tenants > 0:
            serve_tenants(mod, args.steps, args.tenants,
                          directory=args.directory)
        else:
            serve_smscc(mod, args.steps, readers=args.readers,
                        replicas=args.replicas, directory=args.directory)
    else:
        raise SystemExit(f"no serve path for family {mod.FAMILY}")


if __name__ == "__main__":
    main()
