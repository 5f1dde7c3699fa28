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

Where the sweeps run on the sorted live-edge layout
(:func:`repro.core.layout.uses_layout`: ``sparse_impl='sorted'``, or
``'auto'`` on a TPU above the sweep kernel's vertex ceiling), phase 5
sorts the live edges once (``p5_layout``), runs the FW/BW sweeps and,
where no smaller tier takes the region, the full tier on that layout,
and only then the smaller tiers, so the layout is dead while they run.
A table with more than half its slots live takes the scatter sweeps
instead, through one runtime ``lax.cond``.

Tier choice is a runtime ``lax.cond`` inside the one compiled step (no
extra compilations); every tier produces bit-identical labels.  The
chosen tier, the region's vertex/edge counts, the iterations of the
FW/BW fixpoint and of the chosen tier, and the layout the sweeps ran on
are returned as :class:`RepairStats` next to the overflow delta, and
surfaced by ``SCCService.stats()``.  Each phase runs under a
``jax.named_scope`` (``p1_remove_vertex`` .. ``p4_add_edge``,
``p5_layout``, ``p5_reach``, ``p5_tier``), so a profile attributes device
operations to phases.

**Whole-lane dense.**  When ``dense_capacity >= n_vertices`` every region
fits the dense tier, so the step compiles phase 5 as one dense closure of
the whole live graph (``p5_dense``): no FW/BW sweep and no sparse tier is
traced.  Labels outside the region were already the minimum member ids,
so relabelling every live vertex gives the tiered path's labels bit for
bit.  Under ``vmap`` a ``lax.cond`` on a per-lane predicate runs every
branch, so a tenant lane of a small dense graph would otherwise pay the
sweeps and every tier on every step; here it pays one MXU closure.

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
from repro.core import layout as lay, reach, scc
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


def _dense_labels(src, dst, live, region, capacity: int,
                  cfg: gs.GraphConfig):
    """Masked SCC labels of ``region`` from the boolean closure of its
    ``capacity``-square adjacency, and the squarings it took.  The
    closure runs through the injected reach_blockmm boolean mat-mul
    (Pallas on TPU, interpret-mode validation on CPU, jnp oracle under
    impl='xla')."""
    def matmul(a, b):
        return reach_blockmm.bool_matmul(a, b, impl=cfg.dense_matmul_impl)
    lab, _fits = scc.scc_dense_region(src, dst, live, region, capacity,
                                      matmul=matmul)
    return lab, jnp.int32(scc.closure_rounds(capacity))


def _compact_buckets(cfg: gs.GraphConfig) -> tuple:
    """The compact tier's edge buckets, () where it is not compiled."""
    if not 0 < cfg.region_vertex_capacity < cfg.n_vertices:
        return ()
    return tuple(b for b in cfg.region_edge_buckets if b < cfg.edge_capacity)


def _fits_compact(region_v, region_e, cfg: gs.GraphConfig):
    buckets = _compact_buckets(cfg)
    return (region_v <= cfg.region_vertex_capacity) & \
        (region_e <= buckets[-1])


def _below_full(region_v, region_e, cfg: gs.GraphConfig):
    """bool[]: whether a tier below the full one takes the region."""
    fits = jnp.bool_(False)
    if _compact_buckets(cfg):
        fits = _fits_compact(region_v, region_e, cfg)
    if cfg.dense_capacity > 0:
        fits = fits | (region_v <= cfg.dense_capacity)
    return fits


def _full_tier(src, dst, live, region, cfg: gs.GraphConfig, layout=None):
    return scc.scc_static_rounds(
        src, dst, live, region, max_outer=cfg.max_outer,
        max_inner=cfg.max_inner, spec=cfg.label_spec,
        shortcut=cfg.shortcut, impl=cfg.kernel_impl, layout=layout)


def _repair_tiers(src, dst, live, region, region_v, region_e,
                  cfg: gs.GraphConfig, full=None):
    """Masked static-SCC labels of ``region`` by the smallest tier it fits.

    Returns ``(labels, tier, scc_rounds)``: labels valid inside the
    region, the tier code, and the tier's iterations (trim and propagation
    rounds for the sparse tiers, boolean squarings for the dense one).
    ``full``, where given, is the full tier's ``(labels, rounds)``,
    computed beforehand on the sorted layout.
    """
    # The region is the same for every tier; each tier is a cheaper
    # execution of the identical masked static-SCC pass.  Tiers nest
    # smallest-first via lax.cond (one compiled program per cfg -- tier
    # choice is a runtime branch, never a recompile).

    def repair_full(_):
        lab, rounds = (full if full is not None
                       else _full_tier(src, dst, live, region, cfg))
        return lab, jnp.int32(TIER_FULL), rounds

    dispatch = repair_full

    # (2) compact sparse: region fits the bounded compact COO.  Edge
    # slots come from the geometric bucket registry; the smallest
    # bucket that holds the region's live edges wins (lax.switch over
    # static shapes).
    e_buckets = _compact_buckets(cfg)
    if e_buckets:
        vcap = cfg.region_vertex_capacity
        bucket_idx = jnp.minimum(
            jnp.sum((region_e > jnp.asarray(e_buckets, jnp.int32))
                    .astype(jnp.int32)), len(e_buckets) - 1)
        fits_compact = _fits_compact(region_v, region_e, cfg)

        def repair_compact(_):
            # one table-wide compaction, into the largest bucket: a
            # smaller bucket's edge list is its prefix
            csrc, cdst, celive, ids, valid, _, _ = scc.compact_region(
                src, dst, live, region, vcap, e_buckets[-1])

            def compact_branch(ecap):
                def run(_):
                    lab, rounds = scc.compact_labels(
                        csrc[:ecap], cdst[:ecap], celive[:ecap], ids, valid,
                        cfg.n_vertices, max_outer=cfg.max_outer,
                        max_inner=cfg.max_inner, shortcut=cfg.shortcut,
                        impl=cfg.kernel_impl)
                    return lab, jnp.int32(TIER_COMPACT), rounds
                return run

            return jax.lax.switch(bucket_idx,
                                  [compact_branch(b) for b in e_buckets],
                                  None)

        def dispatch(_, fits=fits_compact, below=repair_compact,
                     above=dispatch):
            return jax.lax.cond(fits, below, above, None)

    # (1) dense MXU: small enough to densify
    if cfg.dense_capacity > 0:
        def repair_dense(_):
            lab, rounds = _dense_labels(src, dst, live, region,
                                        cfg.dense_capacity, cfg)
            return lab, jnp.int32(TIER_DENSE), rounds

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
                                   impl=cfg.kernel_impl)
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
                                             impl=cfg.kernel_impl)
        # a key whose probe window earlier keys filled goes in by laying
        # its chain out again, rather than growing the table
        edges, rescued = et.reorder_chain(edges, ops.u, ops.v, dropped,
                                          cfg.max_probes)
        inserted, dropped = inserted | rescued, dropped & ~rescued
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

    def run_repair_dense_lane(_):
        # every live vertex is relabelled by one closure of the live graph
        with jax.named_scope("p5_dense"):
            new_lab, scc_rounds = _dense_labels(src, dst, live, v_alive,
                                                nv, cfg)
        repair = RepairStats(tier=jnp.int32(TIER_DENSE),
                             region_vertices=jnp.sum(v_alive).astype(
                                 jnp.int32),
                             region_edges=jnp.sum(live).astype(jnp.int32),
                             reach_rounds=jnp.int32(0),
                             scc_rounds=scc_rounds,
                             layout=jnp.int32(lay.LAYOUT_NONE))
        return jnp.where(v_alive, new_lab, ccid), repair

    def fw_bw(seed_f, seed_b, layout):
        if layout is not None:
            sweeps = lay.fused_reach if cfg.fuse_fwbw else lay.reach_pair
            return sweeps(layout, seed_f, seed_b, v_alive, cfg.max_inner,
                          spec=cfg.label_spec)
        if cfg.fuse_fwbw:
            return reach.fused_fw_bw_reach(
                src, dst, live, seed_f, seed_b, v_alive, cfg.max_inner,
                spec=cfg.label_spec, impl=cfg.kernel_impl)
        fw, r_fw = reach.forward_reach(
            src, dst, live, seed_f, v_alive, cfg.max_inner,
            spec=cfg.label_spec, impl=cfg.kernel_impl)
        bw, r_bw = reach.backward_reach(
            src, dst, live, seed_b, v_alive, cfg.max_inner,
            spec=cfg.label_spec, impl=cfg.kernel_impl)
        return fw, bw, r_fw + r_bw

    def sweep(seed_f, seed_b, layout=None):
        """The FW/BW sweeps on ``layout`` (None: the scatter sweeps) and
        the region they bound."""
        with jax.named_scope("p5_reach"):
            fw, bw, reach_rounds = fw_bw(seed_f, seed_b, layout)
            region = (m_del | (fw & bw)) & v_alive
            region_v = jnp.sum(region).astype(jnp.int32)
            if layout is not None:
                region_e = lay.count_within(region, layout)
            else:
                region_e = jnp.sum(live & region[src] & region[dst]
                                   ).astype(jnp.int32)
        return region, region_v, region_e, reach_rounds

    def sweep_and_full(seed_f, seed_b, layout):
        """:func:`sweep`, then the full tier's labels on the same edge
        engine where no smaller tier takes the region."""
        region, region_v, region_e, reach_rounds = sweep(seed_f, seed_b,
                                                         layout)
        with jax.named_scope("p5_tier"):
            full = jax.lax.cond(
                _below_full(region_v, region_e, cfg),
                lambda _: (jnp.full((nv,), scc.INT32_MAX, jnp.int32),
                           jnp.int32(0)),
                lambda _: _full_tier(src, dst, live, region, cfg,
                                     layout=layout), None)
        return region, region_v, region_e, reach_rounds, full

    def on_layout(seed_f, seed_b):
        with jax.named_scope("p5_layout"):
            layout = lay.build(src, dst, live, nv)
        return sweep_and_full(seed_f, seed_b, layout)

    def run_repair_tiered(_):
        with jax.named_scope("p5_reach"):
            seed_f = jnp.zeros((nv,), jnp.bool_).at[
                jnp.where(straddle, ops.v, nv)].set(True, mode="drop")
            seed_b = jnp.zeros((nv,), jnp.bool_).at[
                jnp.where(straddle, ops.u, nv)].set(True, mode="drop")
        if lay.uses_layout(cfg.sparse_impl, nv):
            # one sorted layout serves the sweeps and the full tier; the
            # smaller tiers run after it, so their table-wide compaction
            # never holds it
            fits = lay.fits(live)
            region, region_v, region_e, reach_rounds, full = jax.lax.cond(
                fits, lambda _: on_layout(seed_f, seed_b),
                lambda _: sweep_and_full(seed_f, seed_b, None), None)
            layout_code = jnp.where(fits, lay.LAYOUT_SORTED,
                                    lay.LAYOUT_FALLBACK).astype(jnp.int32)
        else:
            region, region_v, region_e, reach_rounds = sweep(seed_f, seed_b)
            full, layout_code = None, jnp.int32(lay.LAYOUT_NONE)
        with jax.named_scope("p5_tier"):
            new_lab, tier, scc_rounds = _repair_tiers(
                src, dst, live, region, region_v, region_e, cfg, full)
        repair = RepairStats(tier=tier, region_vertices=region_v,
                             region_edges=region_e,
                             reach_rounds=reach_rounds,
                             scc_rounds=scc_rounds, layout=layout_code)
        return jnp.where(region, new_lab, ccid), repair

    # a lane whose every region fits the dense tier compiles no other tier
    run_repair = (run_repair_dense_lane if cfg.dense_capacity >= nv
                  else run_repair_tiered)

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
                         impl=cfg.kernel_impl)
    ccid = jnp.where(state.v_alive, lab, cfg.n_vertices)
    return gs.recount_ccs(state._replace(ccid=ccid, gen=state.gen + 1))
