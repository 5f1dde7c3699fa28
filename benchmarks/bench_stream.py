"""Streaming-service throughput: sustained ops/sec across workload mixes.

The paper (Fig 4/5) measures an *on-line* system: a fixed pool of update
threads applies an unbounded stream while readers run SameSCC queries
concurrently.  This bench drives the serving stack through the typed
public API (:class:`repro.api.GraphClient` over
:class:`repro.core.service.SCCService`) -- grow-and-replay, bucketed batch
scheduling, the pipelined in-flight update window, periodic compaction --
with the paper's mix axes:

  update-heavy   90% inserts, no queries        (Fig 4b analogue)
                 measured as build phase + steady-state phase: the
                 steady phase re-adds live edges / removes absent pairs
                 (structure-preserving), so the in-graph repair gate
                 skips phase 5 and the lax.scan super-chunk engine
                 amortizes dispatch -- the paper's claim that most ops
                 leave SCC structure alone is what the row prices
  balanced       50/50 add/remove + queries     (Fig 4a analogue)
  query-heavy    mostly reader batches          (Fig 5 analogue)

then demonstrates the paper's headline *overlap* claim: the same update
mix run once with serial query interleaving (`run_stream`) and once with
per-reader client sessions over a QueryBroker dispatcher
(`run_concurrent_stream --readers N`).  Combined (update+query)
throughput with concurrent readers must exceed the serial baseline --
queries execute against the committed snapshot while the next update step
is still in flight.

The **client-overhead** section prices the facade itself: the same
deterministic stream driven once through typed ops +
``GraphClient.submit_many`` and once through the internal raw-array
entry points, asserting the typed path keeps >= 85% of the internal
path's combined ops/s (facade cost < 15%).

The **replica** section (PR-6) measures the durability stack: a WAL-
backed durable writer plus N read replicas tailing the log serve
closed-loop read-your-writes reader rounds
(:func:`repro.launch.replica.run_replicated_stream`); combined
throughput must scale >= 1.5x from 1 to 2 replicas (staggered replica
poll grids hide replication lag -- a latency-bound regime, so the
scaling is honest on a single core).

The **availability** section (PR-9/PR-10) prices the failure domain:
the same closed-loop replica-served query workload in a steady window
vs a window opened by killing a replica (the supervisor restarts it
mid-window), then closed-loop writes in a steady window vs a window
opened by crashing the *leased* writer (a replica is lease-promoted to
the next WAL epoch mid-window and the client reroutes on ``NotLeader``);
both degraded-window throughput ratios are gated >= 0.5x by
``scripts/ci.sh``.

Finally the **repair-tier** section measures the tiered repair engine on
the paper's locality-of-repair shape (tiny affected regions inside a
large table): the identical small-region workload under the tiered and
untiered configs, per-tier hit counts and median step latency, asserting
the compact-sparse tier's median step beats the full-sparse sweep.

Reported per mix: update ops/s, query ops/s, combined ops/s, number of
compiled step shapes (bounded by bucket-count x (scan-lengths + 1) x
capacity-growth count no matter the stream length: fused-scan + pipelined
+ serial-replay jit entries), table grows, compactions, steady-phase op
count, and the fused-engine counters (``repair_skipped_steps``,
``scanned_chunks``).  ``--json PATH`` *appends* the report to the
perf-trajectory file (``{"runs": [...]}``, one labelled entry per run)
-- ``scripts/ci.sh`` records it as ``BENCH_stream.json`` and gates on
the newest run, so the trajectory accumulates across PRs.

    PYTHONPATH=src python -m benchmarks.bench_stream [--smoke] [--full]
                                                     [--readers N]
                                                     [--json PATH]
                                                     [--label NAME]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro import configs
from repro.configs.smscc import SCAN_LENGTHS
from repro.core import dynamic, graph_state as gs
from repro.core.service import SCCService
from repro.launch import stream
from benchmarks import common


def booted_service(cfg, buckets):
    """Service over a graph with every vertex slot live (singleton SCCs):
    edge inserts then land immediately, so an undersized table must grow.
    Runs the full fused update engine: scan-length super-chunks plus
    proactive growth (growth rehashes happen ahead of a chunk that cannot
    fit, instead of as doomed-dispatch + serial-replay + recompile waves
    on the critical path)."""
    return SCCService(cfg, buckets=buckets, state=gs.all_singletons(cfg),
                      scan_lengths=SCAN_LENGTHS, proactive_grow=True)

MIXES = {
    "update_heavy": dict(add_frac=0.9, query_frac=0.0),
    "balanced": dict(add_frac=0.5, query_frac=0.5),
    "query_heavy": dict(add_frac=0.5, query_frac=1.0),
}


def assert_compile_bound(rep, buckets):
    # grows AND capacity-escalating compactions each mint a new
    # GraphConfig (hence up to len(buckets) fresh step shapes); per
    # config the step entries are one fused-scan program per registered
    # scan length > 1, the single-step pipelined program, and the serial
    # grow-and-replay program -- len(scan_lengths) + 1 per bucket
    n_cfgs = 1 + rep["grows"] + rep["compactions"]
    bound = len(buckets) * (len(SCAN_LENGTHS) + 1) * n_cfgs
    assert rep["compile_count"] <= bound, (
        "per-chunk recompilation detected: "
        f"{rep['compile_count']} compiled shapes for {len(buckets)} "
        f"buckets x ({len(SCAN_LENGTHS)} scan lengths + serial) x "
        f"{n_cfgs} configs")


def run_steady_phase(svc, n_ops, chunk, seed):
    """Structure-preserving churn against the built graph -- the paper's
    steady-state regime where most ops change no SCC structure.

    90% of lanes re-add already-live edges, 10% remove absent pairs; the
    repair gate proves every step's region empty (``repair_skipped_steps``
    advances) and the scan engine amortizes the dispatches, which is
    exactly where the paper's 3-6x mixed-update headline lives."""
    from repro.api import AddEdge, GraphClient, RemoveEdge

    nv = svc.cfg.n_vertices
    live = sorted(svc.edge_set())
    assert live, "steady phase needs a non-empty graph"
    live_set = set(live)
    rng = np.random.default_rng(seed + 0x5EAD)
    client = GraphClient(svc)
    applied = 0
    t0 = time.perf_counter()
    while applied < n_ops:
        n = min(chunk, n_ops - applied)
        ops = []
        for _ in range(n):
            if rng.random() < 0.9:
                a, b = live[int(rng.integers(len(live)))]
                ops.append(AddEdge(int(a), int(b)))
            else:
                while True:
                    a = int(rng.integers(nv))
                    b = int(rng.integers(nv))
                    if (a, b) not in live_set:
                        break
                ops.append(RemoveEdge(a, b))
        client.submit_many(ops)
        applied += n
    wall = time.perf_counter() - t0
    client.close()
    return {"ops": applied, "wall_s": wall}


def run(nv=4096, edge_capacity=4096, n_ops=16384, chunk=512,
        buckets=(128, 512), n_queries=2048, mixes=None, seed=0):
    """One service per mix (fresh table so growth cost is included).

    The update-heavy mix is measured in two phases on one service: the
    build stream (random mixed updates from an undersized table, growth
    included) followed by an equally long steady-state phase
    (:func:`run_steady_phase`).  The row's throughput covers both; the
    ``steady_ops`` column records the split and the
    ``repair_skipped_steps`` / ``scanned_chunks`` columns show the fused
    engine doing its job."""
    smscc = configs.get("smscc")

    def mix_cfg():
        return smscc.config(n_vertices=nv, edge_capacity=edge_capacity,
                            max_probes=64, max_outer=64, max_inner=128)

    # Boot-config step and query shapes are warmed once on a throwaway
    # service (a NOP chunk: the repair gate skips it, so this is pure
    # compilation; the query registry matches run_stream's).  Growth-
    # minted configs still compile inside the timed runs -- growth cost
    # stays included, exactly the PR-4 accounting where later mixes
    # reused the first mix's boot-config jit entries.
    from repro.api import GraphClient, Reachable, SameSCC
    from repro.core.broker import QueryBroker

    warm = booted_service(mix_cfg(), buckets)
    zeros = np.zeros(chunk, np.int32)
    warm._apply_chunk(np.full(chunk, dynamic.NOP, np.int32), zeros, zeros)
    n_reach = min(32, n_queries)
    warm_client = GraphClient(warm, broker=QueryBroker(
        warm, buckets=tuple(sorted({n_queries, n_reach}))))
    warm_client.submit_many([SameSCC(0, 0)] * n_queries)
    warm_client.submit_many([Reachable(0, 0)] * n_reach)
    warm_client.close()

    rows = []
    for name in (mixes or MIXES):
        mix = MIXES[name]
        svc = booted_service(mix_cfg(), buckets)
        rep = stream.run_stream(
            svc, n_ops=n_ops, chunk=chunk, n_queries=n_queries,
            seed=seed, **mix)
        ops, t_update, n_steady = rep["ops"], rep["update_s"], 0
        if name == "update_heavy":
            n_steady = n_ops
            steady = run_steady_phase(svc, n_steady, chunk, seed)
            ops += steady["ops"]
            t_update += steady["wall_s"]
            rep.update(svc.stats())  # cumulative over both phases
        wall = t_update + rep["query_s"]
        rows.append((name, ops,
                     int(ops / t_update) if t_update else 0,
                     rep["queries"], rep["queries_per_s"],
                     int((ops + rep["queries"]) / wall) if wall else 0,
                     rep["compile_count"], rep["grows"],
                     rep["compactions"], rep["edge_capacity"], n_steady,
                     rep["repair_skipped_steps"], rep["scanned_chunks"]))
        assert_compile_bound(rep, buckets)
    return rows


def _warm_caches(fresh, chunk, n_queries):
    """Warm the shared jit cache (step buckets + both query shapes at the
    boot cfg) on a throwaway service, through the same typed-client path
    the timed runs use, so neither timed run is charged compile time the
    other gets for free; growth-minted configs compile identically in
    both runs (same deterministic update stream)."""
    from repro.api import GraphClient, Reachable, SameSCC
    from repro.core.broker import QueryBroker

    warm = fresh()
    # same query-bucket registry as both timed drivers, so the compiled
    # query shapes are all paid for here
    client = GraphClient(warm, broker=QueryBroker(
        warm, buckets=tuple(sorted({n_queries, min(32, n_queries)}))))
    ops = stream.typed_op_stream(warm.cfg.n_vertices, chunk, step=0,
                                 add_frac=0.5, seed=999)
    client.submit_many(ops)
    client.submit_many([SameSCC(0, 0)] * n_queries)
    client.submit_many([Reachable(0, 0)] * min(32, n_queries))
    client.close()


def run_overlap(nv=4096, edge_capacity=4096, n_ops=16384, chunk=512,
                buckets=(128, 512), n_queries=2048, readers=2, seed=0,
                reps=2):
    """Serial-reader baseline vs concurrent reader pool on the SAME update
    mix (balanced): the paper's Fig 4/5 overlap demonstration.

    Each mode is run ``reps`` times and scored on its best rep.  The
    section is wall-clock-sensitive (threads + single-shot streams), and
    single-shot scoring is what produced the phantom pr4 -> pr5
    "regression" in the trajectory: controlled A/B on one machine shows
    the pr5 engine is ~25% *faster* on this exact workload, while the
    committed single-shot numbers moved 137,925 -> 66,700 across two CI
    containers whose min-of-reps client-overhead sections agree within
    1.5%.  Best-of-reps makes the trajectory row mean what it says."""
    smscc = configs.get("smscc")

    def fresh():
        cfg = smscc.config(n_vertices=nv, edge_capacity=edge_capacity,
                           max_probes=64, max_outer=64, max_inner=128)
        return booted_service(cfg, buckets)

    _warm_caches(fresh, chunk, n_queries)

    # both modes are scored on full wall clock (workload generation and
    # thread startup included) so the comparison is symmetric
    serial, serial_combined = None, 0
    for _ in range(reps):
        t0 = time.perf_counter()
        rep = stream.run_stream(fresh(), n_ops=n_ops, add_frac=0.5,
                                query_frac=1.0, chunk=chunk,
                                n_queries=n_queries, seed=seed)
        wall = time.perf_counter() - t0
        combined = int((rep["ops"] + rep["queries"]) / wall)
        if combined >= serial_combined:
            serial, serial_combined = rep, combined
    conc = None
    for _ in range(reps):
        rep = stream.run_concurrent_stream(fresh(), n_ops=n_ops,
                                           readers=readers, add_frac=0.5,
                                           chunk=chunk,
                                           n_queries=n_queries, seed=seed)
        if conc is None or rep["combined_per_s"] > conc["combined_per_s"]:
            conc = rep
    assert_compile_bound(conc, buckets)
    rows = [("serial_readers", serial["ops"], serial["ops_per_s"],
             serial["queries"], serial["queries_per_s"],
             serial_combined, 0),
            (f"concurrent_x{readers}", conc["ops"], conc["ops_per_s"],
             conc["queries"], conc["queries_per_s"],
             conc["combined_per_s"], readers)]
    assert conc["combined_per_s"] > serial_combined, (
        "no reader/updater overlap: concurrent combined throughput "
        f"{conc['combined_per_s']} ops/s did not beat the serial "
        f"baseline {serial_combined} ops/s")
    return rows


def run_client_overhead(nv=4096, edge_capacity=4096, n_ops=8192,
                        chunk=512, buckets=(128, 512), n_queries=1024,
                        seed=0, reps=3, max_overhead=0.15):
    """Price the typed facade: the same deterministic update+query stream
    through (a) typed ops + ``GraphClient.submit_many`` and (b) the
    internal raw-array entry points (``SCCService._apply_chunk`` +
    direct snapshot queries) -- identical device work, so the delta is
    pure client-layer overhead (op objects, encoding, broker futures).

    Asserts the typed path sustains >= ``1 - max_overhead`` of the
    internal path's combined ops/s (min-of-``reps`` wall times, plus a
    small absolute slack so tiny smoke runs don't flake on scheduler
    noise)."""
    from repro.api import GraphClient, SameSCC
    from repro.core.broker import QueryBroker
    from repro.launch import workload

    smscc = configs.get("smscc")

    def fresh():
        cfg = smscc.config(n_vertices=nv, edge_capacity=edge_capacity,
                           max_probes=64, max_outer=64, max_inner=128)
        return booted_service(cfg, buckets)

    n_chunks = n_ops // chunk
    raw, typed, qpairs, typed_q = [], [], [], []
    for step in range(n_chunks):
        ops = workload.op_stream(nv, chunk, step=step, add_frac=0.5,
                                 seed=seed)
        arrs = (np.asarray(ops.kind), np.asarray(ops.u),
                np.asarray(ops.v))
        raw.append(arrs)
        typed.append(stream.typed_op_stream(nv, chunk, step=step,
                                            add_frac=0.5, seed=seed))
        rng = np.random.default_rng(seed + step)
        qu = rng.integers(0, nv, n_queries)
        qv = rng.integers(0, nv, n_queries)
        qpairs.append((qu, qv))
        typed_q.append([SameSCC(int(a), int(b)) for a, b in zip(qu, qv)])

    def time_direct():
        svc = fresh()
        t0 = time.perf_counter()
        for arrs, (qu, qv) in zip(raw, qpairs):
            svc._apply_chunk(*arrs)
            svc.same_scc(qu, qv)
        return time.perf_counter() - t0

    def time_typed():
        svc = fresh()
        # broker bucket == query batch size so both paths run identical
        # device shapes; only the facade differs
        client = GraphClient(svc, broker=QueryBroker(
            svc, buckets=(n_queries,)))
        t0 = time.perf_counter()
        for ops, qs in zip(typed, typed_q):
            client.submit_many(ops)
            client.submit_many(qs)
        dt = time.perf_counter() - t0
        client.close()
        return dt

    time_direct()  # shared-cache warmup for both paths' jit entries
    time_typed()
    t_direct = min(time_direct() for _ in range(reps))
    t_typed = min(time_typed() for _ in range(reps))
    total = n_chunks * (chunk + n_queries)
    direct_ps = int(total / t_direct)
    typed_ps = int(total / t_typed)
    rows = [("internal_raw", total, direct_ps, round(t_direct, 4)),
            ("typed_client", total, typed_ps, round(t_typed, 4))]
    overhead_frac = round(max(0.0, t_typed / t_direct - 1.0), 4)
    assert t_typed <= t_direct * (1 + max_overhead) + 0.05, (
        f"GraphClient facade too expensive: {t_typed:.4f}s typed vs "
        f"{t_direct:.4f}s internal "
        f"({(t_typed / t_direct - 1) * 100:.1f}% > {max_overhead:.0%})")
    return rows, overhead_frac


def run_repair_tiers(nv=8192, edge_capacity=2 ** 15, cycle=8, steps=48,
                     touched_cycles=2, seed=0, assert_speedup=True):
    """The repair-tier section: small-region repair on a large graph.

    The base graph is ``nv / cycle`` disjoint directed cycles (one SCC
    each).  Every step removes the edges of a few random cycles and
    re-adds them in the same batch, so the affected region is just those
    cycles' members -- the paper's locality-of-repair shape: the region
    stays tiny while the table stays huge.  A handful of steps are forced
    tiny (dense tier) or huge (full tier) so every tier reports a hit.

    The identical deterministic op sequence runs once under the tiered
    config and once under the untiered full-sparse baseline; per-step wall
    times are grouped by the tier the tiered run reported.  Asserts the
    compact-sparse tier's median step beats the full-sparse baseline's
    median over the very same steps.
    """
    smscc = configs.get("smscc")
    n_cycles = nv // cycle
    vcap = max(64, touched_cycles * cycle * 4)

    def build(tiered: bool):
        kw = dict(n_vertices=nv, edge_capacity=edge_capacity,
                  max_probes=64, max_outer=64, max_inner=128)
        if tiered:
            # dense tier sized to one cycle, compact to a few, full beyond
            kw.update(dense_capacity=cycle, region_vertex_capacity=vcap,
                      region_edge_buckets=(256, 4096))
        else:
            kw.update(dense_capacity=0, region_vertex_capacity=0)
        cfg = smscc.config(**kw)
        base = np.arange(nv, dtype=np.int32)
        src = base
        dst = (base // cycle) * cycle + (base + 1) % cycle
        state = gs.from_arrays(cfg, src, dst)
        assert int(state.overflow) == 0
        state = dynamic.recompute(state, cfg)
        return cfg, state

    def cycle_toggle(cs):
        """Remove + re-add every edge of the given cycles in ONE batch:
        region == those cycles' members, graph unchanged after the step."""
        u = np.concatenate([c * cycle + np.arange(cycle) for c in cs]
                           ).astype(np.int32)
        v = np.concatenate([c * cycle + (np.arange(cycle) + 1) % cycle
                            for c in cs]).astype(np.int32)
        n = u.shape[0]
        kind = np.concatenate([np.full(n, dynamic.REM_EDGE, np.int32),
                               np.full(n, dynamic.ADD_EDGE, np.int32)])
        return (np.stack([kind, np.concatenate([u, u]),
                          np.concatenate([v, v])]), None)

    # full-tier shape: cross edges chaining > vcap worth of cycles into one
    # giant SCC (then an untimed undo batch splits them back apart)
    span_cycles = min(n_cycles, 2 * vcap // cycle + 2)
    heads = (np.arange(span_cycles, dtype=np.int32) * cycle + cycle - 1)
    tails = ((np.arange(1, span_cycles + 1, dtype=np.int32) % span_cycles)
             * cycle)
    full_add = np.stack([np.full(span_cycles, dynamic.ADD_EDGE, np.int32),
                         heads, tails])
    full_rm = np.stack([np.full(span_cycles, dynamic.REM_EDGE, np.int32),
                        heads, tails])

    rng = np.random.default_rng(seed)
    batches = []
    for s in range(steps):
        if s % 12 == 10:   # full tier
            batches.append((full_add, full_rm))
        elif s % 12 == 11:  # dense tier: one cycle == dense_capacity
            batches.append(cycle_toggle([int(rng.integers(0, n_cycles))]))
        else:               # compact tier: a few cycles
            batches.append(cycle_toggle(
                rng.choice(n_cycles, size=touched_cycles, replace=False)))

    def pad(arr, n):
        k, u, v = arr
        pk = np.full(n, dynamic.NOP, np.int32)
        pu = np.zeros(n, np.int32)
        pv = np.zeros(n, np.int32)
        pk[:k.shape[0]] = k
        pu[:k.shape[0]] = u
        pv[:k.shape[0]] = v
        return dynamic.make_ops(pk, pu, pv)

    n_lanes = max(max(b[0].shape[1], 0 if b[1] is None else b[1].shape[1])
                  for b in batches)

    def drive(cfg, state):
        import jax
        # warm the (single) step shape so no run is charged compile time
        warm = pad((np.array([dynamic.NOP], np.int32),
                    np.zeros(1, np.int32), np.zeros(1, np.int32)), n_lanes)
        out = dynamic.apply_batch_async(state, warm, cfg)
        jax.block_until_ready(out[0].ccid)
        state = out[0]
        times, tiers = [], []
        for arr, undo in batches:
            ops = pad(arr, n_lanes)
            t0 = time.perf_counter()
            state, _, _, rstats = dynamic.apply_batch_async(state, ops,
                                                            cfg)
            jax.block_until_ready(state.ccid)
            times.append(time.perf_counter() - t0)
            tiers.append(int(rstats.tier))
            if undo is not None:  # restore the base graph out-of-band
                state, _, _, _ = dynamic.apply_batch_async(
                    state, pad(undo, n_lanes), cfg)
                jax.block_until_ready(state.ccid)
        return np.asarray(times), tiers

    cfg_t, st_t = build(tiered=True)
    cfg_f, st_f = build(tiered=False)
    times_t, tiers_t = drive(cfg_t, st_t)
    times_f, _ = drive(cfg_f, st_f)

    counts = {name: tiers_t.count(code)
              for code, name in enumerate(dynamic.TIER_NAMES)}
    rows, med = [], {}
    for code, name in enumerate(dynamic.TIER_NAMES):
        idx = [i for i, t in enumerate(tiers_t) if t == code]
        med_t = float(np.median(times_t[idx])) if idx else None
        med_f = float(np.median(times_f[idx])) if idx else None
        med[name] = {"tiered_s": med_t, "baseline_full_s": med_f,
                     "steps": len(idx)}
        rows.append((name, len(idx),
                     round(med_t * 1e3, 3) if idx else "",
                     round(med_f * 1e3, 3) if idx else "",
                     round(med_f / med_t, 2) if idx else ""))
    assert counts["compact"] > 0, "workload never hit the compact tier"
    speedup = (med["compact"]["baseline_full_s"]
               / med["compact"]["tiered_s"])
    if assert_speedup:
        assert speedup > 1.0, (
            "compact-sparse repair did not beat full-sparse on the "
            f"small-region workload: {med['compact']['tiered_s']:.6f}s vs "
            f"{med['compact']['baseline_full_s']:.6f}s per step")
    report = {"nv": nv, "edge_capacity": edge_capacity, "cycle": cycle,
              "steps": steps, "tier_counts": counts,
              "median_step_s": med,
              "compact_vs_full_speedup": round(speedup, 3)}
    return rows, report


def run_replicas(counts=(1, 2), min_scaling=1.5, **stream_kw):
    """Replica-scaling section (PR-6): closed-loop read-your-writes
    rounds against a durable writer + N WAL-tailing read replicas
    (:func:`repro.launch.replica.run_replicated_stream`).

    Every reader round commits a touch write and then queries at
    ``AT_LEAST`` of its session floor, so each round must wait out
    replication lag; the replicas' staggered poll grids cut the
    expected freshness wait from ~poll/2 to ~poll/2N, which is where
    combined throughput scales with replica count on a latency-bound
    (not compute-bound) regime -- honest scaling on a 1-core host.
    Asserts >= ``min_scaling``x combined ops/s at ``counts[-1]``
    replicas vs ``counts[0]``."""
    import tempfile

    from repro.launch.replica import run_replicated_stream

    rows, combined = [], {}
    for n in counts:
        with tempfile.TemporaryDirectory() as d:
            rep = run_replicated_stream(d, replicas=n, **stream_kw)
        rows.append((f"replicas_x{n}", rep["ops"], rep["ops_per_s"],
                     rep["queries"], rep["queries_per_s"],
                     rep["combined_per_s"], n, rep["routed_stale"],
                     rep["replica_gen_waits"]))
        combined[n] = rep["combined_per_s"]
    scaling = round(combined[counts[-1]] / combined[counts[0]], 3)
    assert scaling >= min_scaling, (
        f"replica scaling too weak: {counts[-1]} replicas gave only "
        f"{scaling}x the combined throughput of {counts[0]} "
        f"({combined[counts[-1]]} vs {combined[counts[0]]} ops/s); "
        f"floor is {min_scaling}x")
    report = {"counts": list(counts),
              "rows": _dicts(rows, REPLICA_HEADER),
              "scaling": scaling, "floor": min_scaling}
    return rows, report


def run_tenancy(n_tenants=6, steps=20, nv=256, chunk=16,
                min_speedup=2.0):
    """Multi-tenant section (PR-8): the same N per-tenant workloads
    driven once through N *sequential* single-tenant
    :class:`SCCService` instances and once through ONE
    :class:`repro.tenancy.MultiTenantService` (vmapped
    :class:`~repro.tenancy.engine.TenantEngine` behind the admission
    :class:`~repro.tenancy.queue.WorkQueue`, one submitter thread per
    tenant).

    The multi-tenant path coalesces the T tenants' same-shape chunks
    into one vmapped dispatch and pays ONE host sync per wave (ok/ovf
    refs + fill-stats ride the same transfer), where the sequential
    baseline pays per chunk: a dispatch, the commit-gen sync, and the
    compaction-probe fill-stats sync.  Sized for the many-small-tenants
    serving regime (small per-tenant chunks) where that fixed per-chunk
    cost dominates the sequential path.  Asserts aggregate multi-tenant
    ops/s >= ``min_speedup`` x the sequential baseline, the engine's
    compiled-entry registry stayed under its
    ``(tenant_batches x scan_lengths x buckets x cfgs)`` bound, and the
    final per-tenant labellings are **bit-identical** between the two
    paths (tenancy is an execution strategy, not a semantics change).

    Reports queue depth / flush causes / pool hit rate and stacked-lane
    occupancy."""
    import threading

    from repro.launch import workload
    from repro.tenancy import MultiTenantService

    mod = configs.get("smscc")
    cfg = mod.config(n_vertices=nv, edge_capacity=max(nv, 256),
                     max_probes=64, max_outer=64, max_inner=64)
    buckets = (chunk,)

    def chunks_for(i):
        out = []
        for step in range(steps):
            ops = workload.op_stream(
                nv, chunk, step=step,
                add_frac=1.0 if step == 0 else 0.7, seed=1000 + i)
            out.append((np.asarray(ops.kind, np.int32),
                        np.asarray(ops.u, np.int32),
                        np.asarray(ops.v, np.int32)))
        return out

    workloads = [chunks_for(i) for i in range(n_tenants)]
    timed_ops = n_tenants * (steps - 1) * chunk

    # --- sequential baseline: N independent single-tenant services ----
    seq = [SCCService(cfg, buckets=buckets, scan_lengths=SCAN_LENGTHS)
           for _ in range(n_tenants)]
    for svc, wl in zip(seq, workloads):     # warm the jit caches
        svc._apply_chunk(*wl[0])
    t0 = time.perf_counter()
    for svc, wl in zip(seq, workloads):
        for k, u, v in wl[1:]:
            svc._apply_chunk(k, u, v)
    seq_wall = time.perf_counter() - t0

    # --- multi-tenant: one engine + queue, a submitter per tenant -----
    mts = MultiTenantService(cfg, buckets=buckets,
                             scan_lengths=SCAN_LENGTHS,
                             tenant_batches=(1, 2, n_tenants),
                             coalesce_ops=n_tenants * chunk,
                             flush_deadline_s=0.01)
    tids = [mts.create_tenant() for _ in range(n_tenants)]
    sessions = [mts.session(tid) for tid in tids]

    def drive_one(sess, wl, lo, hi):
        for k, u, v in wl[lo:hi]:
            sess._apply_ops(k, u, v)

    def fan_out(lo, hi):
        ts = [threading.Thread(target=drive_one, args=(s, w, lo, hi))
              for s, w in zip(sessions, workloads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    fan_out(0, 1)                           # warm the vmapped entries
    t0 = time.perf_counter()
    fan_out(1, steps)
    multi_wall = time.perf_counter() - t0

    # bit-identity: the vmapped/coalesced path is an execution strategy,
    # not a semantics change
    for svc, sess, tid in zip(seq, sessions, tids):
        assert int(sess.gen) == int(svc.gen), \
            f"tenant {tid}: gen {int(sess.gen)} != oracle {int(svc.gen)}"
        assert np.array_equal(np.asarray(sess.state.ccid),
                              np.asarray(svc.state.ccid)), \
            f"tenant {tid}: labelling diverged from single-tenant oracle"

    agg = mts.stats()
    eng, q = agg["engine"], agg["queue"]
    assert eng["compile_count"] <= eng["compile_bound"], (
        f"tenant-entry compile bound violated: {eng['compile_count']} > "
        f"{eng['compile_bound']}")
    seq_rate = round(timed_ops / seq_wall, 1)
    multi_rate = round(timed_ops / multi_wall, 1)
    speedup = round(seq_wall / multi_wall, 3)
    assert speedup >= min_speedup, (
        f"multi-tenant coalescing too weak: {n_tenants} tenants gave "
        f"only {speedup}x the sequential baseline ({multi_rate} vs "
        f"{seq_rate} ops/s); floor is {min_speedup}x")
    rows = [("sequential_x%d" % n_tenants, timed_ops, seq_rate,
             round(seq_wall, 3), 1.0),
            ("multi_tenant_x%d" % n_tenants, timed_ops, multi_rate,
             round(multi_wall, 3), speedup)]
    per_tenant = []
    for tid in tids:
        ts = mts.tenant_stats(tid)
        per_tenant.append({"tid": tid, "gen": ts["gen"],
                           "fallback_chunks": ts["fallback_chunks"]})
    report = {"tenants": n_tenants, "steps": steps, "chunk": chunk,
              "ops": timed_ops,
              "seq_ops_per_s": seq_rate, "multi_ops_per_s": multi_rate,
              "speedup": speedup, "floor": min_speedup,
              "compile_count": eng["compile_count"],
              "compile_bound": eng["compile_bound"],
              "occupancy": eng["occupancy"],
              "queue": {k: q[k] for k in
                        ("depth_max_ops", "waves", "rejects",
                         "flush_causes", "pool")},
              "per_tenant": per_tenant}
    mts.close()
    return rows, report


def run_availability_section(window_s=0.8, replicas=2, min_ratio=0.5,
                             min_write_ratio=0.5):
    """Degraded-window serving (PR-9/PR-10): closed-loop query
    throughput through a supervised ReplicaSet in a steady window vs a
    window where one replica is killed and supervisor-restarted, then
    closed-loop *write* throughput in a steady window vs a window where
    the leased writer is crashed and a replica promoted mid-window
    (:func:`repro.launch.chaos.run_availability`).  The query caller is
    latency-bound, so transparent failover should keep the read ratio
    near 1.0; writes pay one lease TTL plus the takeover, so the write
    ratio floor is 0.5x over a window that dwarfs the TTL (losing more
    than half of it means promotion or client reroute is broken)."""
    from repro.launch.chaos import run_availability

    rep = run_availability(window_s=window_s, replicas=replicas)
    rep["floor"] = min_ratio
    rep["write_floor"] = min_write_ratio
    rows = [
        ("steady", rep["steady_per_s"], rep["steady_faults"], 1.0),
        ("replica_killed", rep["faulted_per_s"], rep["faulted_faults"],
         rep["ratio"]),
        ("write_steady", rep["write_steady_per_s"],
         rep["write_steady_faults"], 1.0),
        ("writer_crashed", rep["write_faulted_per_s"],
         rep["write_faulted_faults"], rep["write_availability"]),
    ]
    assert rep["ratio"] >= min_ratio, (
        f"availability collapsed under a replica kill: degraded-window "
        f"throughput ratio {rep['ratio']} < {min_ratio} floor")
    assert rep["write_availability"] >= min_write_ratio, (
        f"write availability collapsed under writer loss: faulted-"
        f"window ratio {rep['write_availability']} < {min_write_ratio} "
        f"floor")
    assert rep["promotions"] >= 1, (
        "the writer crash never promoted a replica: the write-"
        "availability window measured a dead store")
    return rows, rep


HEADER = ["mix", "ops", "ops_per_s", "queries", "queries_per_s",
          "combined_per_s", "compiled_shapes", "grows", "compactions",
          "final_capacity", "steady_ops", "repair_skipped_steps",
          "scanned_chunks"]
OVERLAP_HEADER = ["mode", "ops", "ops_per_s", "queries", "queries_per_s",
                  "combined_per_s", "readers"]
OVERHEAD_HEADER = ["path", "ops", "combined_per_s", "wall_s"]
REPAIR_HEADER = ["tier", "steps", "tiered_median_ms",
                 "full_baseline_median_ms", "speedup"]
REPLICA_HEADER = ["mode", "ops", "ops_per_s", "queries", "queries_per_s",
                  "combined_per_s", "replicas", "routed_stale",
                  "gen_waits"]
TENANCY_HEADER = ["mode", "ops", "ops_per_s", "wall_s", "speedup"]
AVAIL_HEADER = ["phase", "per_s", "typed_faults", "ratio"]


def _dicts(rows, header):
    return [dict(zip(header, r)) for r in rows]


def _kernel_impl_info(nv, edge_capacity):
    """What the ``'auto'`` sparse_impl resolved to for this run's shapes
    -- recorded so a trajectory point taken on a TPU host (Pallas sweeps)
    is never compared against a CPU point (XLA oracle) by accident."""
    from repro.kernels.frontier_expand import ops as frontier_ops
    from repro.kernels.hash_probe import ops as hash_probe_ops
    return {
        "sparse_impl": "auto",
        "frontier_expand": frontier_ops.resolve_impl("auto", nv),
        "hash_probe": hash_probe_ops.resolve_impl("auto", edge_capacity),
    }


def append_report(path, report):
    """Append-friendly perf trajectory: ``{"runs": [...]}`` with one
    labelled entry per recorded run.  A pre-schema single-run file (the
    PR-4 format) is migrated in place as the first trajectory point."""
    runs = []
    if os.path.exists(path):
        with open(path) as f:
            existing = json.load(f)
        if isinstance(existing, dict) and \
                isinstance(existing.get("runs"), list):
            runs = existing["runs"]
        elif isinstance(existing, dict) and "bench" in existing:
            existing.setdefault("label", "pr4-baseline")
            runs = [existing]  # pre-schema single-run file: migrate
        else:
            # never silently destroy the committed perf trajectory --
            # an unrecognized file is the operator's to resolve
            raise RuntimeError(
                f"{path} exists but is not a bench_stream trajectory "
                f"(neither a runs-schema nor a pre-schema report); "
                f"refusing to overwrite it")
    runs.append(report)
    with open(path, "w") as f:
        json.dump({"schema": "bench_stream/v2", "runs": runs}, f,
                  indent=2)
        f.write("\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU-friendly run (CI: exercises grow + "
                         "replay + both mix extremes + the steady-state "
                         "gate/scan phase + reader overlap + the facade-"
                         "overhead bound + the repair-tier speedup "
                         "end-to-end)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale graph (slow; accelerator advised)")
    ap.add_argument("--readers", type=int, default=2,
                    help="reader threads for the overlap comparison")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="append the machine-readable report to the "
                         "perf-trajectory file recorded by scripts/ci.sh")
    ap.add_argument("--label", default=None,
                    help="trajectory label for this run (default: mode)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        # capacity starts undersized on purpose so the smoke run also
        # covers table growth; chunk = 4 x the large bucket so the scan
        # engine's K=4 super-chunks are exercised end-to-end
        buckets = (32, 128)
        nv_used, cap_used = 256, 256
        rows = run(nv=nv_used, edge_capacity=cap_used, n_ops=1024,
                   chunk=512, buckets=buckets, n_queries=256,
                   mixes=("update_heavy", "query_heavy"))
        overlap = run_overlap(nv=256, edge_capacity=1024, n_ops=1024,
                              chunk=128, buckets=buckets, n_queries=256,
                              readers=args.readers)
        overhead, overhead_frac = run_client_overhead(
            nv=256, edge_capacity=1024, n_ops=1024, chunk=128,
            buckets=buckets, n_queries=256)
        repair, repair_rep = run_repair_tiers(nv=4096,
                                              edge_capacity=2 ** 14,
                                              steps=36)
        replicas, replicas_rep = run_replicas()
        tenancy, tenancy_rep = run_tenancy(n_tenants=6, steps=16,
                                           nv=256, chunk=16)
        avail, avail_rep = run_availability_section(window_s=0.6)
    elif args.full:
        buckets = (1024, 4096)
        # chunk = 4 x the large bucket: the mixes run K=4 super-chunks
        nv_used, cap_used = 2 ** 17, 2 ** 18
        rows = run(nv=nv_used, edge_capacity=cap_used, n_ops=2 ** 17,
                   chunk=2 ** 14, buckets=buckets, n_queries=2 ** 15)
        overlap = run_overlap(nv=2 ** 17, edge_capacity=2 ** 18,
                              n_ops=2 ** 17, chunk=4096,
                              buckets=buckets, n_queries=2 ** 15,
                              readers=args.readers)
        overhead, overhead_frac = run_client_overhead(
            nv=2 ** 17, edge_capacity=2 ** 18, n_ops=2 ** 16,
            chunk=4096, buckets=buckets, n_queries=2 ** 14)
        repair, repair_rep = run_repair_tiers(nv=2 ** 16,
                                              edge_capacity=2 ** 18,
                                              steps=60, touched_cycles=4)
        replicas, replicas_rep = run_replicas(counts=(1, 2, 3),
                                              n_ops=1920, nv=2048)
        tenancy, tenancy_rep = run_tenancy(n_tenants=6, steps=48,
                                           nv=512, chunk=16)
        avail, avail_rep = run_availability_section(window_s=1.5,
                                                    replicas=3)
    else:
        buckets = (128, 512)
        nv_used, cap_used = 4096, 4096
        rows = run(buckets=buckets, chunk=2048)
        overlap = run_overlap(buckets=buckets, readers=args.readers)
        overhead, overhead_frac = run_client_overhead(buckets=buckets)
        repair, repair_rep = run_repair_tiers()
        replicas, replicas_rep = run_replicas(counts=(1, 2, 3))
        tenancy, tenancy_rep = run_tenancy(n_tenants=6, steps=24,
                                           nv=512, chunk=16)
        avail, avail_rep = run_availability_section()
    common.emit(rows, HEADER)
    common.emit(overlap, OVERLAP_HEADER)
    common.emit(overhead, OVERHEAD_HEADER)
    print(f"client overhead_frac: {overhead_frac}")
    common.emit(repair, REPAIR_HEADER)
    common.emit(replicas, REPLICA_HEADER)
    print(f"replica scaling: {replicas_rep['scaling']}x at "
          f"{replicas_rep['counts'][-1]} vs {replicas_rep['counts'][0]} "
          f"replicas (floor {replicas_rep['floor']}x)")
    common.emit(tenancy, TENANCY_HEADER)
    print(f"tenancy speedup: {tenancy_rep['speedup']}x aggregate over "
          f"{tenancy_rep['tenants']} sequential single-tenant services "
          f"(floor {tenancy_rep['floor']}x, compile "
          f"{tenancy_rep['compile_count']}/{tenancy_rep['compile_bound']})")
    common.emit(avail, AVAIL_HEADER)
    print(f"availability under replica kill: {avail_rep['ratio']}x of "
          f"the steady window ({avail_rep['restarts']} supervisor "
          f"restart(s), floor {avail_rep['floor']}x)")
    print(f"write availability under writer loss: "
          f"{avail_rep['write_availability']}x of the steady window "
          f"({avail_rep['promotions']} promotion(s), floor "
          f"{avail_rep['write_floor']}x)")
    if args.json:
        mode = "smoke" if args.smoke else "full" if args.full else "default"
        report = {
            "bench": "bench_stream",
            "mode": mode,
            "label": args.label or mode,
            "n_buckets": len(buckets),
            "n_scan_lengths": len(SCAN_LENGTHS),
            "repair_tier_count": len(dynamic.TIER_NAMES),
            "mixes": _dicts(rows, HEADER),
            "overlap": _dicts(overlap, OVERLAP_HEADER),
            "client_overhead": {
                "paths": _dicts(overhead, OVERHEAD_HEADER),
                "overhead_frac": overhead_frac,
            },
            "repair_tiers": repair_rep,
            "replicas": replicas_rep,
            "tenancy": tenancy_rep,
            "availability": avail_rep,
            "kernel_impl": _kernel_impl_info(nv_used, cap_used),
        }
        append_report(args.json, report)
        print(f"appended run '{report['label']}' to {args.json}")


if __name__ == "__main__":
    main()
