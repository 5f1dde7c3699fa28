"""SMSCC: batched fully-dynamic SCC maintenance (the paper's contribution).

The paper's concurrency unit is a POSIX thread applying one operation under
fine-grained locks; ours is a *lane* of an operation batch applied by one
compiled dataflow step.  ``apply_batch`` consumes a :class:`GraphState` and
an :class:`OpBatch` and produces the state after *some* linearization of the
batch plus per-op boolean results matching the paper's method contracts:

  AddVertex(u)     true iff u was absent          (paper Alg. 20)
  RemoveVertex(u)  true iff u was present         (paper Alg. 18)
  AddEdge(u,v)     true iff u,v present & edge absent   (paper Alg. 15)
  RemoveEdge(u,v)  true iff u,v present & edge present  (paper Alg. 16)

The fixed linearization order inside a batch is
``RemoveVertex -> RemoveEdge -> AddVertex -> AddEdge`` with ties broken by
lane index (scatter-min claims), so results always equal a sequential
history -- the batch-atomic analogue of the paper's linearizability.

Repair (the paper's §5.1/§5.2, *locality of repair*):

  * deletions can only split the SCCs they touched: those classes are
    collected in ``M_del``;
  * insertions can only merge SCCs on a ``v ⇝ u`` path: every vertex of any
    such path lies in ``FW(new heads) ∩ BW(new tails)`` = ``C_ins``;
  * one masked static-SCC pass over ``M = M_del ∪ C_ins`` restores the
    partition; labels outside M are untouched.

M is a union of (pre-batch) SCCs plus fully-included broken classes, and
every post-batch SCC that changed has all its internal paths inside M, so
the masked recomputation is exact (proof sketch in DESIGN.md §2).

The masked pass itself is *tiered* so its per-round work is proportional
to the region, not the table (the other half of locality of repair):

  tier 0 dense    |M| <= dense_capacity: densify the region and close it
                  with boolean mat-muls through the injected Pallas
                  ``reach_blockmm`` kernel (MXU on TPU);
  tier 1 compact  |M| <= region_vertex_capacity and the region's live
                  edges fit a bucket of ``region_edge_buckets``: compact
                  the region once into bounded static sub-arrays and run
                  the scc_static fixpoints there -- O(region edges) per
                  round;
  tier 2 full     overflow fallback: scc_static over the full edge table.

Tier choice is a runtime ``lax.cond`` inside the one compiled step (no
extra compilations); every tier produces bit-identical labels.  The
chosen tier, the region's vertex/edge counts and the iterations of the
FW/BW fixpoint and of the chosen tier are returned as
:class:`RepairStats` next to the overflow delta, and surfaced by
``SCCService.stats()``.  Each phase runs under a ``jax.named_scope``
(``p1_remove_vertex`` .. ``p4_add_edge``, ``p5_reach``, ``p5_tier``), so
a profile attributes device operations to phases.

Two step-level fusions keep the *update-heavy* path fast (the paper's
Fig 4/5 regime, where most ops do not change SCC structure):

  * the **repair gate** (``GraphConfig.repair_gate``, on by default) wraps
    all of phase 5 in a ``lax.cond`` on a cheap in-graph predicate --
    a step with no straddling insert and no deletion-affected SCC member
    has a provably empty region, so the whole repair is skipped
    (``RepairStats.tier == TIER_SKIP``) at O(batch) cost, bit-identically;
  * the **scan engine** (:func:`apply_batch_scan`) runs K same-bucket
    chunks through the step inside one compiled ``lax.scan``, carrying the
    state and stacking per-step ``ok``/overflow/:class:`RepairStats`
    outputs, so the service dispatches (and host-syncs) once per
    super-chunk instead of per chunk.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import edge_table as et
from repro.core import graph_state as gs
from repro.core import reach, scc
from repro.kernels.reach_blockmm import ops as reach_blockmm

ADD_EDGE = 0
REM_EDGE = 1
ADD_VERTEX = 2
REM_VERTEX = 3
NOP = 4

INT32_MAX = jnp.iinfo(jnp.int32).max

# Repair-tier codes / names / stats pytree live in graph_state (the scan
# entry stacks RepairStats leaves, and keeping the pytree next to
# GraphState avoids a dynamic<->graph_state import cycle); re-exported
# here because this module is the tier dispatcher's home.
TIER_DENSE = gs.TIER_DENSE
TIER_COMPACT = gs.TIER_COMPACT
TIER_FULL = gs.TIER_FULL
TIER_SKIP = gs.TIER_SKIP
TIER_NAMES = gs.TIER_NAMES
RepairStats = gs.RepairStats


class OpBatch(NamedTuple):
    kind: jax.Array  # int32[B] in {ADD_EDGE..NOP}
    u: jax.Array     # int32[B]
    v: jax.Array     # int32[B]  (ignored for vertex ops)


def make_ops(kind, u, v) -> OpBatch:
    return OpBatch(kind=jnp.asarray(kind, jnp.int32),
                   u=jnp.asarray(u, jnp.int32),
                   v=jnp.asarray(v, jnp.int32))


def _first_claim(cand, target, nv, b):
    """Lane wins iff it is the lowest-indexed candidate lane for its target
    vertex -- the batched analogue of 'first thread to get the lock'."""
    idx = jnp.arange(b, dtype=jnp.int32)
    claims = jnp.full((nv + 1,), b, jnp.int32)
    claims = claims.at[jnp.where(cand, target, nv)].min(
        jnp.where(cand, idx, b))
    return cand & (claims[target] == idx)


def _repair_tiers(src, dst, live, region, region_v, region_e,
                  cfg: gs.GraphConfig):
    """Masked static-SCC labels of ``region`` by the smallest tier it fits.

    Returns ``(labels, tier, scc_rounds)``: labels valid inside the
    region, the tier code, and the tier's iterations (trim and propagation
    rounds for the sparse tiers, boolean squarings for the dense one).
    """
    # The region is the same for every tier; each tier is a cheaper
    # execution of the identical masked static-SCC pass.  Tiers nest
    # smallest-first via lax.cond (one compiled program per cfg -- tier
    # choice is a runtime branch, never a recompile).
    nv = cfg.n_vertices

    def repair_full(_):
        lab, rounds = scc.scc_static_rounds(
            src, dst, live, region, max_outer=cfg.max_outer,
            max_inner=cfg.max_inner, spec=cfg.label_spec,
            shortcut=cfg.shortcut, impl=cfg.sparse_impl)
        return lab, jnp.int32(TIER_FULL), rounds

    dispatch = repair_full

    # (2) compact sparse: region fits the bounded compact COO.  Edge
    # slots come from the geometric bucket registry; the smallest
    # bucket that holds the region's live edges wins (lax.switch over
    # static shapes).
    e_buckets = tuple(b for b in cfg.region_edge_buckets
                      if b < cfg.edge_capacity)
    if 0 < cfg.region_vertex_capacity < nv and e_buckets:
        vcap = cfg.region_vertex_capacity

        def compact_branch(ecap):
            def run(_):
                lab, _fits, rounds = scc.scc_compact_region(
                    src, dst, live, region, vcap, ecap,
                    max_outer=cfg.max_outer, max_inner=cfg.max_inner,
                    shortcut=cfg.shortcut, impl=cfg.sparse_impl)
                return lab, jnp.int32(TIER_COMPACT), rounds
            return run

        branches = [compact_branch(b) for b in e_buckets]
        bucket_idx = jnp.minimum(
            jnp.sum((region_e > jnp.asarray(e_buckets, jnp.int32))
                    .astype(jnp.int32)), len(e_buckets) - 1)
        fits_compact = (region_v <= vcap) & (region_e <= e_buckets[-1])

        def repair_compact(_):
            return jax.lax.switch(bucket_idx, branches, None)

        def dispatch(_, fits=fits_compact, below=repair_compact,
                     above=dispatch):
            return jax.lax.cond(fits, below, above, None)

    # (1) dense MXU: small enough to densify; the adjacency closure
    # runs through the injected reach_blockmm boolean mat-mul (Pallas
    # on TPU, interpret-mode validation on CPU, jnp oracle under
    # impl='xla').
    if cfg.dense_capacity > 0:
        def repair_dense(_):
            def matmul(a, b):
                return reach_blockmm.bool_matmul(
                    a, b, impl=cfg.dense_matmul_impl)
            lab, _fits = scc.scc_dense_region(src, dst, live, region,
                                              cfg.dense_capacity,
                                              matmul=matmul)
            return (lab, jnp.int32(TIER_DENSE),
                    jnp.int32(scc.closure_rounds(cfg.dense_capacity)))

        fits_dense = region_v <= cfg.dense_capacity

        def dispatch(_, fits=fits_dense, below=repair_dense,
                     above=dispatch):
            return jax.lax.cond(fits, below, above, None)

    return dispatch(None)


def _apply_batch_impl(state: gs.GraphState, ops: OpBatch,
                      cfg: gs.GraphConfig):
    """One batch-atomic SMSCC step.

    Returns ``(new_state, ok: bool[B], ovf_delta: int32[], RepairStats)``.
    The overflow *delta* and the repair stats are dedicated output buffers
    (never aliased to the input state) so a pipelined caller can donate
    ``state`` into the next step and still inspect them later without
    touching donated memory.
    """
    nv = cfg.n_vertices
    b = ops.kind.shape[0]
    vid = jnp.arange(nv, dtype=jnp.int32)

    v_alive = state.v_alive
    ccid = state.ccid  # working labels; sentinel nv for dead slots
    edges = state.edges
    ok = jnp.zeros((b,), jnp.bool_)

    in_range = (ops.u >= 0) & (ops.u < nv) & \
        jnp.where((ops.kind == ADD_EDGE) | (ops.kind == REM_EDGE),
                  (ops.v >= 0) & (ops.v < nv), True)

    # ---- Phase 1: RemoveVertex --------------------------------------------
    with jax.named_scope("p1_remove_vertex"):
        is_remv = (ops.kind == REM_VERTEX) & in_range
        cand = is_remv & v_alive[jnp.clip(ops.u, 0, nv - 1)]
        win_remv = _first_claim(cand, ops.u, nv, b)
        ok = jnp.where(win_remv, True, ok)
        killed = jnp.zeros((nv,), jnp.bool_).at[
            jnp.where(win_remv, ops.u, nv)].set(True, mode="drop")
        # deletion-affected classes: the old class of every killed vertex
        affected_rep = jnp.zeros((nv + 1,), jnp.bool_)
        affected_rep = affected_rep.at[
            jnp.where(killed, jnp.minimum(ccid, nv), nv)].set(
                True, mode="drop")
        v_alive = v_alive & ~killed
        # the paper's "trim after RemoveVertex": drop all incident edges
        # at once
        edges, _ = et.remove_incident(edges, killed)
        ccid = jnp.where(killed, nv, ccid)

    # ---- Phase 2: RemoveEdge ----------------------------------------------
    with jax.named_scope("p2_remove_edge"):
        is_reme = (ops.kind == REM_EDGE) & in_range
        ends_ok = v_alive[jnp.clip(ops.u, 0, nv - 1)] & \
            v_alive[jnp.clip(ops.v, 0, nv - 1)]
        edges, removed = et.remove(edges, ops.u, ops.v, cfg.max_probes,
                                   enable=is_reme & ends_ok,
                                   impl=cfg.sparse_impl)
        ok = jnp.where(removed, True, ok)
        same_class = ccid[jnp.clip(ops.u, 0, nv - 1)] == \
            ccid[jnp.clip(ops.v, 0, nv - 1)]
        hit = removed & same_class
        affected_rep = affected_rep.at[
            jnp.where(hit,
                      jnp.minimum(ccid[jnp.clip(ops.u, 0, nv - 1)], nv),
                      nv)].set(True, mode="drop")

    # ---- Phase 3: AddVertex (paper: new SCC at CCHead, ccCount++) ---------
    with jax.named_scope("p3_add_vertex"):
        is_addv = (ops.kind == ADD_VERTEX) & in_range
        cand = is_addv & ~v_alive[jnp.clip(ops.u, 0, nv - 1)]
        win_addv = _first_claim(cand, ops.u, nv, b)
        ok = jnp.where(win_addv, True, ok)
        born = jnp.zeros((nv,), jnp.bool_).at[
            jnp.where(win_addv, ops.u, nv)].set(True, mode="drop")
        v_alive = v_alive | born
        ccid = jnp.where(born, vid, ccid)  # fresh singleton SCC

    # ---- Phase 4: AddEdge --------------------------------------------------
    with jax.named_scope("p4_add_edge"):
        is_adde = (ops.kind == ADD_EDGE) & in_range
        ends_ok = v_alive[jnp.clip(ops.u, 0, nv - 1)] & \
            v_alive[jnp.clip(ops.v, 0, nv - 1)]
        enable = is_adde & ends_ok
        edges, inserted, dropped = et.insert(edges, ops.u, ops.v,
                                             cfg.max_probes, enable=enable,
                                             impl=cfg.sparse_impl)
        ok = jnp.where(inserted, True, ok)
        # overflow accounting straight from the table's own
        # probe-exhaustion report -- the host must grow the table and
        # replay these lanes.
        ovf = jnp.sum(dropped).astype(jnp.int32)

    # ---- Phase 5: unified localized repair ---------------------------------
    src, dst, live = edges.src, edges.dst, edges.state == et.LIVE

    # deletion side: all members of affected classes (live labels are < nv,
    # so the junk slot [nv] written by inactive lanes is never read here)
    m_del = v_alive & affected_rep[jnp.minimum(ccid, nv)]
    # insertion side: FW(inserted heads) ∩ BW(inserted tails), but only for
    # edges that straddle two current classes (paper Alg. 15 line 226 check)
    straddle = inserted & (ccid[jnp.clip(ops.u, 0, nv - 1)] !=
                           ccid[jnp.clip(ops.v, 0, nv - 1)])

    def run_repair(_):
        with jax.named_scope("p5_reach"):
            seed_f = jnp.zeros((nv,), jnp.bool_).at[
                jnp.where(straddle, ops.v, nv)].set(True, mode="drop")
            seed_b = jnp.zeros((nv,), jnp.bool_).at[
                jnp.where(straddle, ops.u, nv)].set(True, mode="drop")
            if cfg.fuse_fwbw:
                fw, bw, reach_rounds = reach.fused_fw_bw_reach(
                    src, dst, live, seed_f, seed_b, v_alive, cfg.max_inner,
                    spec=cfg.label_spec, impl=cfg.sparse_impl)
            else:
                fw, r_fw = reach.forward_reach(
                    src, dst, live, seed_f, v_alive, cfg.max_inner,
                    spec=cfg.label_spec, impl=cfg.sparse_impl)
                bw, r_bw = reach.backward_reach(
                    src, dst, live, seed_b, v_alive, cfg.max_inner,
                    spec=cfg.label_spec, impl=cfg.sparse_impl)
                reach_rounds = r_fw + r_bw
            region = (m_del | (fw & bw)) & v_alive
            region_v = jnp.sum(region).astype(jnp.int32)
            region_e = jnp.sum(live & region[src] & region[dst]
                               ).astype(jnp.int32)
        with jax.named_scope("p5_tier"):
            new_lab, tier, scc_rounds = _repair_tiers(
                src, dst, live, region, region_v, region_e, cfg)
        repair = RepairStats(tier=tier, region_vertices=region_v,
                             region_edges=region_e,
                             reach_rounds=reach_rounds,
                             scc_rounds=scc_rounds)
        return jnp.where(region, new_lab, ccid), repair

    if cfg.repair_gate:
        # In-graph repair gate: the region is M_del ∪ (FW ∩ BW), FW/BW are
        # seeded only by straddling inserts, so `no straddle and no
        # deletion-affected member` proves the region EMPTY -- every tier's
        # masked pass would be the identity on ccid.  Skipping is therefore
        # exact (bit-identical labels), not merely conservative; the
        # conservative direction (repair may run on a batch that turns out
        # structure-preserving, e.g. a RemoveEdge inside an SCC that stays
        # strongly connected) errs safe.  lax.cond keeps it one compiled
        # program: a structure-preserving step costs O(batch + NV) instead
        # of O(region fixpoint).
        need_repair = jnp.any(m_del) | jnp.any(straddle)

        def skip_repair(_):
            return ccid, gs.repair_skipped()

        ccid, repair = jax.lax.cond(need_repair, run_repair, skip_repair,
                                    None)
    else:
        ccid, repair = run_repair(None)

    ccid = jnp.where(v_alive, ccid, nv)

    new_state = gs.GraphState(
        v_alive=v_alive,
        ccid=ccid,
        edges=edges,
        n_ccs=state.n_ccs,  # recomputed below
        gen=state.gen + 1,
        overflow=state.overflow + ovf,
    )
    new_state = gs.recount_ccs(new_state)
    return new_state, ok, ovf, repair


@partial(jax.jit, static_argnames=("cfg",))
def apply_batch(state: gs.GraphState, ops: OpBatch, cfg: gs.GraphConfig):
    """One batch-atomic SMSCC step.  Returns (new_state, ok: bool[B])."""
    new_state, ok, _, _ = _apply_batch_impl(state, ops, cfg)
    return new_state, ok


# In-flight variants for the concurrent-reader pipeline: both return the
# per-step overflow delta and repair telemetry as extra outputs so the host
# can defer its only sync point behind a window of dispatched steps.  The
# donating entry hands the input state's buffers to XLA for reuse — callers
# must guarantee nothing else (in particular no committed reader snapshot)
# still references them.
apply_batch_async = jax.jit(_apply_batch_impl, static_argnames=("cfg",))
_apply_batch_donated = jax.jit(_apply_batch_impl, static_argnames=("cfg",),
                               donate_argnums=(0,))


def apply_batch_inflight(state: gs.GraphState, ops: OpBatch,
                         cfg: gs.GraphConfig, *, donate: bool = False):
    """Dispatch one step without forcing any host sync.

    Returns ``(new_state, ok, ovf_delta, RepairStats)`` as in-flight
    device values.  With ``donate=True`` the input state's buffers are
    donated to the output (saves a full state copy per step on
    accelerators; ignored with a warning on CPU, where XLA does not
    implement donation).
    """
    fn = _apply_batch_donated if donate else apply_batch_async
    return fn(state, ops, cfg)


# --------------------------------------------------------------------------
# Fused multi-chunk scan engine
# --------------------------------------------------------------------------

def _apply_batch_scan_impl(state: gs.GraphState, ops: OpBatch,
                           cfg: gs.GraphConfig):
    """K stacked bucket-shaped chunks through the full 5-phase step inside
    ONE compiled program.

    ``ops`` carries ``int32[K, B]`` leaves (K same-bucket chunks stacked
    along a scan axis); ``lax.scan`` threads the :class:`GraphState` carry
    through the K steps and stacks the per-step outputs, so the host pays
    one dispatch (and later one transfer) per *super-chunk* instead of per
    chunk.  Each scan step is the unmodified ``_apply_batch_impl`` -- the
    linearization, per-op results, overflow accounting, and labels are
    bit-identical to K sequential ``apply_batch`` calls.

    Returns ``(new_state, ok: bool[K, B], ovf_delta: int32[K],
    RepairStats with int32[K] leaves)``; all three trailing outputs are
    dedicated buffers (never aliased to the carry), so a donating caller
    can hand ``state`` to the next super-chunk and still resolve them.
    """

    def body(st, op):
        st, ok, ovf, repair = _apply_batch_impl(st, op, cfg)
        return st, (ok, ovf, repair)

    state, (ok, ovf, repair) = jax.lax.scan(body, state, ops)
    return state, ok, ovf, repair


apply_batch_scan = jax.jit(_apply_batch_scan_impl, static_argnames=("cfg",))
_apply_batch_scan_donated = jax.jit(_apply_batch_scan_impl,
                                    static_argnames=("cfg",),
                                    donate_argnums=(0,))


def apply_batch_scan_inflight(state: gs.GraphState, ops: OpBatch,
                              cfg: gs.GraphConfig, *, donate: bool = False):
    """Dispatch one K-chunk super-chunk without forcing any host sync.

    The scan analogue of :func:`apply_batch_inflight`: one jit entry per
    ``(K, bucket, cfg)`` from the service's scan-length registry, so the
    compile count stays bounded by ``buckets x scan_lengths`` per config.
    """
    fn = _apply_batch_scan_donated if donate else apply_batch_scan
    return fn(state, ops, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def recompute(state: gs.GraphState, cfg: gs.GraphConfig) -> gs.GraphState:
    """Full static SCC of the current graph (bulk-load / oracle path)."""
    src, dst, live = gs.edge_coo(state)
    lab = scc.scc_static(src, dst, live, state.v_alive,
                         max_outer=cfg.max_outer, max_inner=cfg.max_inner,
                         spec=cfg.label_spec, shortcut=cfg.shortcut,
                         impl=cfg.sparse_impl)
    ccid = jnp.where(state.v_alive, lab, cfg.n_vertices)
    return gs.recount_ccs(state._replace(ccid=ccid, gen=state.gen + 1))
