"""Static parallel SCC: trim -> coloring -> masked backward sweep.

This is the repair engine the dynamic algorithm (:mod:`repro.core.dynamic`)
calls on the *affected region only* -- the TPU-native stand-in for the
paper's limited Tarjan (merge) and limited Kosaraju (split) passes.  The
algorithm is the Slota-multistep / Orzan-coloring family, chosen because
every phase is an edge-parallel map + segment reduction (VPU) or, on the
dense path, a blocked boolean mat-mul (MXU):

  outer round (bounded by ``max_outer``):
    1. **trim** to fixpoint: peel vertices with zero live in- or out-degree
       inside the unassigned set; each peeled vertex is its own SCC.  This
       kills DAG-like tails that would cost the coloring pass one round each.
    2. **color**: forward min-label propagation; colors are constant on SCCs
       and every color class has exactly one *root* r with color[r] == r,
       which is the minimum vertex id of its SCC whenever it is assignable.
    3. **backward sweep**: from all roots simultaneously, walk reversed
       edges restricted to the root's color class; every vertex reached is
       strongly connected to its root.  Assign ``ccid = color`` there.

Labels are *canonical*: ccid[v] == min vertex id of v's SCC, matching the
paper's invariant that an SCC's identity is stable while its membership is.

The repair engine runs in three tiers over the same affected region
(:mod:`repro.core.dynamic` dispatches per step, smallest first):

  * dense (`scc_dense_region`): gather the region into a compact adjacency
    matrix and close it with O(log R) boolean mat-mul squarings -- the
    Pallas ``reach_blockmm`` kernel's job on the MXU;
  * compact sparse (`scc_compact_region`): gather region vertices and live
    intra-region edges once into bounded static sub-arrays
    (`compact_region`) and rerun the trim/color/backward fixpoints there,
    so each round costs O(region edges) instead of O(table capacity);
  * full sparse (`scc_static` over the full COO): the overflow fallback
    when the region exceeds every compact capacity.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import reach

INT32_MAX = jnp.iinfo(jnp.int32).max


def _degrees(src, dst, emask, nv):
    indeg = jax.ops.segment_sum(emask.astype(jnp.int32), dst, nv)
    outdeg = jax.ops.segment_sum(emask.astype(jnp.int32), src, nv)
    return indeg, outdeg


def trim(src, dst, live, unassigned, vid, ccid, max_iters: int):
    """Iteratively peel zero-in/out-degree vertices into singleton SCCs.
    Returns (unassigned, ccid, rounds)."""
    nv = unassigned.shape[0]

    def body(carry):
        unassigned, ccid = carry
        emask = live & unassigned[src] & unassigned[dst]
        indeg, outdeg = _degrees(src, dst, emask, nv)
        peel = unassigned & ((indeg == 0) | (outdeg == 0))
        ccid = jnp.where(peel, vid, ccid)
        return (unassigned & ~peel, ccid), jnp.any(peel)

    (unassigned, ccid), rounds = reach._fixpoint(body, (unassigned, ccid),
                                                 max_iters)
    return unassigned, ccid, rounds


@partial(jax.jit, static_argnames=("max_outer", "max_inner", "spec",
                                   "shortcut", "impl"))
def scc_static(src, dst, live, active, *, max_outer: int, max_inner: int,
               spec=None, shortcut: bool = False, impl: str = "xla"):
    """SCC labels of the subgraph induced by ``active`` over live edges.

    Returns int32[NV]: min-member-id label for active vertices, INT32_MAX
    sentinel elsewhere.  ``max_outer`` bounds coloring rounds (>= number of
    'layers' of SCCs after trimming); ``max_inner`` bounds propagation
    rounds (>= region diameter).  ``spec`` optionally pins the NV-array
    sharding inside the fixpoints (GraphConfig.label_spec).
    """
    return scc_static_rounds(src, dst, live, active, max_outer=max_outer,
                             max_inner=max_inner, spec=spec,
                             shortcut=shortcut, impl=impl)[0]


def scc_static_rounds(src, dst, live, active, *, max_outer: int,
                      max_inner: int, spec=None, shortcut: bool = False,
                      impl: str = "xla"):
    """:func:`scc_static` (unjitted) with its iteration count: returns
    ``(ccid, rounds)``, rounds being every trim and propagation iteration,
    summed over the outer rounds."""
    nv = active.shape[0]
    vid = jnp.arange(nv, dtype=jnp.int32)
    ccid = jnp.full((nv,), INT32_MAX, jnp.int32)
    unassigned = active

    def outer_cond(carry):
        unassigned, _, it, _ = carry
        return jnp.any(unassigned) & (it < max_outer)

    def outer_body(carry):
        unassigned, ccid, it, rounds = carry
        # (1) trim
        unassigned, ccid, r_trim = trim(src, dst, live, unassigned, vid,
                                        ccid, max_inner)
        # (2) forward-min and backward-min witnesses within unassigned:
        # fwd[v] = min-priority vertex reaching v, bwd[v] = min-priority
        # vertex v reaches.  A vertex sits in a finished SCC exactly when
        # fwd == bwd == w (then w ⇝ v and v ⇝ w).  Both sweeps are
        # min-label propagations, so both accelerate under hashed-priority
        # pointer doubling (shortcut=True) -- unlike the classic coloring
        # + boolean backward sweep, whose backward phase is pinned at
        # O(diameter) rounds.
        if shortcut:
            fwd, r_fwd = reach.propagate_min_prio(
                src, dst, live, unassigned, max_inner, spec=spec,
                impl=impl)
            bwd, r_bwd = reach.propagate_min_prio(
                dst, src, live, unassigned, max_inner, spec=spec,
                impl=impl)
            done = unassigned & (fwd == bwd) & (fwd < nv)
            # canonical label = min member id of each witness group
            grp = jnp.where(done, fwd, nv)
            min_id = jnp.full((nv + 1,), INT32_MAX, jnp.int32).at[
                grp].min(jnp.where(done, vid, INT32_MAX))
            ccid = jnp.where(done, min_id[jnp.minimum(fwd, nv)], ccid)
        else:
            init = jnp.where(unassigned, vid, INT32_MAX)
            fwd, r_fwd = reach.propagate_min_labels(
                src, dst, live, init, unassigned, max_inner, spec=spec,
                impl=impl)
            bwd, r_bwd = reach.propagate_min_labels(
                dst, src, live, init, unassigned, max_inner, spec=spec,
                impl=impl)
            done = unassigned & (fwd == bwd)
            ccid = jnp.where(done, fwd, ccid)
        unassigned = unassigned & ~done
        return unassigned, ccid, it + 1, rounds + r_trim + r_fwd + r_bwd

    _, ccid, _, rounds = jax.lax.while_loop(
        outer_cond, outer_body,
        (unassigned, ccid, jnp.int32(0), jnp.int32(0)))
    return ccid, rounds


# ---------------------------------------------------------------------------
# Compact-sparse region path
# ---------------------------------------------------------------------------

def _enumerate_region(region_mask, capacity: int):
    """Stable (ascending-global-id) enumeration of region members into
    ``capacity`` slots.  Returns ``(pos_of int32[NV], ids int32[capacity],
    valid bool[capacity])``; non-members and overflow land in a clamped
    junk slot that ``ids`` never sees.  Order preservation is what both
    compact tiers' bit-identity rests on: the min compact index and the
    min global id of any subset name the same vertex."""
    nv = region_mask.shape[0]
    pos_of = jnp.cumsum(region_mask) - 1
    pos_of = jnp.where(region_mask, pos_of, capacity)
    pos_of = jnp.minimum(pos_of, capacity).astype(jnp.int32)
    ids = jnp.full((capacity + 1,), -1, jnp.int32).at[pos_of].set(
        jnp.arange(nv, dtype=jnp.int32), mode="drop")[:capacity]
    return pos_of, ids, ids >= 0


def compact_region(src, dst, live, region_mask, v_capacity: int,
                   e_capacity: int):
    """Pack the affected region into bounded compact COO arrays.

    Region vertices are enumerated stably (ascending global id) into
    ``v_capacity`` slots; live intra-region edges into ``e_capacity``
    compact-index edge slots.  Returns
    ``(csrc, cdst, celive, ids, valid, pos_of, fits)``:

      * ``csrc/cdst`` int32[EC], ``celive`` bool[EC] -- the compacted edge
        list over compact vertex indices [0, v_capacity);
      * ``ids`` int32[VC] -- global id of each compact slot (-1 unused),
        ``valid`` its occupancy mask, ``pos_of`` int32[NV] the inverse map;
      * ``fits`` bool[] -- False when either capacity is exceeded (the
        caller must fall back to the full-sparse sweep).

    The enumeration is order-preserving, so the min compact index and the
    min global id of any vertex subset name the same vertex -- canonical
    min-member-id labels survive the compaction round trip bit-exactly.
    """
    v_count = jnp.sum(region_mask)
    e_in = live & region_mask[src] & region_mask[dst]
    e_count = jnp.sum(e_in)
    fits = (v_count <= v_capacity) & (e_count <= e_capacity)
    pos_of, ids, valid = _enumerate_region(region_mask, v_capacity)
    # stable enumeration of live intra-region edges; overflowing or
    # non-region edges land in the sliced-off junk slot
    epos = jnp.cumsum(e_in) - 1
    epos = jnp.where(e_in, epos, e_capacity)
    epos = jnp.minimum(epos, e_capacity).astype(jnp.int32)
    cap_src = jnp.minimum(pos_of[src], v_capacity - 1)
    cap_dst = jnp.minimum(pos_of[dst], v_capacity - 1)
    csrc = jnp.zeros((e_capacity + 1,), jnp.int32).at[epos].set(
        cap_src, mode="drop")[:e_capacity]
    cdst = jnp.zeros((e_capacity + 1,), jnp.int32).at[epos].set(
        cap_dst, mode="drop")[:e_capacity]
    celive = jnp.zeros((e_capacity + 1,), jnp.bool_).at[epos].set(
        e_in, mode="drop")[:e_capacity]
    return csrc, cdst, celive, ids, valid, pos_of, fits


def scc_compact_region(src, dst, live, region_mask, v_capacity: int,
                       e_capacity: int, *, max_outer: int, max_inner: int,
                       shortcut: bool = False, impl: str = "xla"):
    """SCC labels of the region via the compact-sparse tier.

    Gathers the region once into static ``(v_capacity, e_capacity)``
    sub-arrays and reruns the :func:`scc_static` fixpoints there, so every
    trim/color/backward round costs O(region) gathers and scatters instead
    of O(table capacity).  Returns ``(ccid int32[NV], fits bool[],
    rounds int32[])`` -- labels valid where ``region_mask`` (INT32_MAX
    sentinel elsewhere) and bit-identical to :func:`scc_static` on the
    uncompacted ``(src, dst, live, region_mask)`` operands: both
    produce canonical min-member-id labels and the compact enumeration is
    order-preserving; ``rounds`` as :func:`scc_static_rounds` counts them.
    """
    nv = region_mask.shape[0]
    csrc, cdst, celive, ids, valid, _, fits = compact_region(
        src, dst, live, region_mask, v_capacity, e_capacity)
    # no spec: the whole point is that compact operands are small enough to
    # stay replicated, round after round
    clab, rounds = scc_static_rounds(
        csrc, cdst, celive, valid, max_outer=max_outer,
        max_inner=max_inner, shortcut=shortcut, impl=impl)
    # a slot scc_static left unassigned (sentinel; only possible when
    # max_outer was exhausted) must stay the sentinel globally too, exactly
    # as the full-sparse tier would report it -- never a clipped real id
    glab = jnp.where(valid & (clab < v_capacity),
                     ids[jnp.clip(clab, 0, v_capacity - 1)], INT32_MAX)
    ccid = jnp.full((nv,), INT32_MAX, jnp.int32)
    ccid = ccid.at[jnp.where(valid, ids, nv)].set(glab, mode="drop")
    return ccid, fits, rounds


# ---------------------------------------------------------------------------
# Dense (MXU) region path
# ---------------------------------------------------------------------------

def gather_region(src, dst, live, region_mask, capacity: int):
    """Pack up to ``capacity`` region vertices into a dense adjacency.

    Returns (adj bool[R, R], ids int32[R], valid bool[R], fits bool[]).
    ``fits`` is False when the region has more members than ``capacity``;
    the caller must then fall back to the sparse path.
    """
    count = jnp.sum(region_mask)
    fits = count <= capacity
    pos_of, ids, valid = _enumerate_region(region_mask, capacity)
    # scatter live intra-region edges into the dense block
    e_in = live & region_mask[src] & region_mask[dst]
    r, c = pos_of[src], pos_of[dst]
    r = jnp.where(e_in, r, capacity)  # OOB -> dropped
    c = jnp.where(e_in, c, capacity)
    adj = jnp.zeros((capacity + 1, capacity + 1), jnp.bool_)
    adj = adj.at[r, c].set(True, mode="drop")
    return adj[:capacity, :capacity], ids, valid, fits


def closure_rounds(r: int) -> int:
    """Boolean squarings :func:`closure_dense` makes for an R x R block."""
    return max(1, math.ceil(math.log2(max(r, 2))))


def closure_dense(adj, matmul=None):
    """Reflexive-transitive closure via O(log R) boolean squarings.

    ``matmul`` is the boolean-semiring product hook; the Pallas kernel
    (kernels.reach_blockmm) is injected here by the dynamic engine, with the
    pure-jnp product as the oracle/fallback.
    """
    r = adj.shape[0]
    reach_m = adj | jnp.eye(r, dtype=jnp.bool_)
    if matmul is None:
        def matmul(a, b):
            return jnp.einsum("ij,jk->ik", a.astype(jnp.float32),
                              b.astype(jnp.float32)) > 0.0
    for _ in range(closure_rounds(r)):
        reach_m = reach_m | matmul(reach_m, reach_m)
    return reach_m


def scc_dense_region(src, dst, live, region_mask, capacity: int,
                     matmul=None):
    """SCC labels for a (small) region on the dense MXU path.

    Returns (ccid_region int32[NV] -- labels only valid where region_mask --
    fits bool[]).  Labels are min-member-id, identical to ``scc_static``.
    """
    nv = region_mask.shape[0]
    adj, ids, valid, fits = gather_region(src, dst, live, region_mask,
                                          capacity)
    clo = closure_dense(adj, matmul)
    both = clo & clo.T  # strongly connected pairs
    both = both & valid[None, :] & valid[:, None]
    # label = min id over the strongly-connected row
    big = jnp.where(valid, ids, INT32_MAX)
    lab = jnp.min(jnp.where(both, big[None, :], INT32_MAX), axis=1)
    ccid = jnp.full((nv,), INT32_MAX, jnp.int32)
    ccid = ccid.at[jnp.where(valid, ids, nv)].set(lab, mode="drop")
    return ccid, fits
