"""Dynamic SCC-Graph state: the TPU-native analogue of the paper's SCC-Graph.

The paper (Sa, 2018) stores the graph as a three-level lazy linked list
(SCC list -> vertex list -> edge list) with per-node locks and logical
(``marked``) deletion.  On TPU there is no shared mutable heap, so the same
information lives in fixed-capacity dense arrays:

  * vertices are slots ``0..n_vertices-1`` with an ``v_alive`` mask
    (``marked`` inverted),
  * edges live in an open-addressing hash table (:mod:`repro.core.edge_table`)
    whose ``(src, dst, live)`` columns double as a COO edge list for the
    vectorized sweeps,
  * the SCC membership ("which vertex list do I sit in") is a label array
    ``ccid[v]`` whose canonical value is the minimum vertex id in the SCC --
    labels form a semilattice under ``min`` which is what lets concurrent
    (batched) updates merge without locks.

Everything in this module is a pure function of pytrees; all shapes are
static so every operation jits and pjits.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import edge_table as et

# Sentinel label meaning "no SCC / dead vertex".  Any value >= n_vertices works.
INT32_MAX = jnp.iinfo(jnp.int32).max

# Repair-tier codes reported in RepairStats.tier, ordered by preference:
# the phase-5 dispatcher picks the smallest tier the affected region fits,
# and TIER_SKIP records that the repair gate proved the step needed no
# repair at all (the region was empty, so every tier would be a no-op).
TIER_DENSE = 0     # region densified, closed on the MXU (reach_blockmm)
TIER_COMPACT = 1   # region compacted to bounded COO, sparse fixpoints there
TIER_FULL = 2      # full-table sparse fixpoints (overflow fallback)
TIER_SKIP = 3      # repair gate: structure-preserving step, phase 5 skipped
TIER_NAMES = ("dense", "compact", "full", "skipped")


class RepairStats(NamedTuple):
    """Per-step repair telemetry (device scalars; stacked to int32[K]
    leaves by the ``apply_batch_scan`` entry and resolved lazily by the
    service next to the overflow delta)."""
    tier: jax.Array             # int32[]  TIER_DENSE..TIER_SKIP
    region_vertices: jax.Array  # int32[]  |M_del ∪ (FW ∩ BW)| this step
    region_edges: jax.Array     # int32[]  live intra-region edges this step
    reach_rounds: jax.Array     # int32[]  FW/BW fixpoint iterations
    scc_rounds: jax.Array       # int32[]  the chosen tier's iterations


def repair_skipped() -> RepairStats:
    """The stats a gated (structure-preserving) step reports: no tier ran,
    no region was materialized, no fixpoint iterated."""
    zero = jnp.int32(0)
    return RepairStats(tier=jnp.int32(TIER_SKIP), region_vertices=zero,
                       region_edges=zero, reach_rounds=zero,
                       scc_rounds=zero)


@dataclasses.dataclass(frozen=True)
class GraphConfig:
    """Static (non-traced) capacities of the dynamic graph."""

    n_vertices: int  # vertex-slot capacity; ids in [0, n_vertices)
    edge_capacity: int  # hash-table capacity; power of two; keep <=50% load
    max_probes: int = 64  # linear-probing bound per batched table op
    max_outer: int = 128  # SCC peel rounds bound
    max_inner: int = 256  # reachability / fixpoint rounds bound (>= diameter)
    dense_capacity: int = 0  # >0 enables dense blocked repair path (Pallas)
    # which reach_blockmm.bool_matmul implementation the dense tier feeds:
    # 'auto' = Pallas MXU kernel on TPU / interpret-mode validation on CPU,
    # 'pallas' / 'pallas_interpret' force those, 'xla' = jnp oracle fallback
    dense_matmul_impl: str = "auto"
    # which implementation backs the *sparse* hot loop: every FW/BW
    # fixpoint round (kernels.frontier_expand segment-min) and every
    # edge-table probe (kernels.hash_probe fused sweep).  'auto' = Pallas
    # on TPU within the kernels' size ceilings, XLA scatter/probe-loop
    # otherwise; 'pallas' / 'pallas_interpret' force the kernel; 'xla' is
    # the differential oracle the fuzz suites A/B against.  Unlike
    # dense_matmul_impl, CPU 'auto' resolves to 'xla' (not interpret):
    # these sweeps are always-on, and interpret-executing them would
    # regress every step by orders of magnitude -- the interpret path is
    # exercised by the forced-impl test suites instead.
    sparse_impl: str = "auto"
    # compact-sparse repair tier: >0 (and < n_vertices) compacts affected
    # regions of at most this many vertices into bounded sub-arrays so each
    # fixpoint round costs O(region) instead of O(table capacity)
    region_vertex_capacity: int = 0
    # geometric registry of compact-COO edge capacities (static shapes, so
    # the per-config compile count stays bounded by the registry size);
    # buckets >= edge_capacity are dropped at dispatch (no smaller than the
    # full table means no win).  The smallest bucket that holds the
    # region's live edges is chosen per step; none fitting -> full sweep.
    region_edge_buckets: tuple = (256, 4096, 65536)
    # optional PartitionSpec for the NV-sized label/frontier arrays inside
    # the repair fixpoints (None = replicated + all-reduce merge; a
    # 'model'-axis spec turns the merges into reduce-scatter-style
    # exchanges -- the §Perf collective-term knob)
    label_spec: object = None
    # fuse the FW and BW reachability sweeps of the repair into ONE
    # fixpoint over a stacked [2, NV] frontier: halves both the round
    # count and the per-round collective launches (§Perf knob)
    fuse_fwbw: bool = False
    # Shiloach-Vishkin pointer doubling in the coloring sweep: label
    # chains collapse in O(log diameter) rounds (§Perf knob)
    shortcut: bool = False
    # in-graph repair gate: wrap all of phase 5 (the FW/BW sweeps and the
    # tiered masked static-SCC pass) in a lax.cond on a cheap on-device
    # predicate computed from the batch -- a step whose region is provably
    # empty (no straddling insert, no deletion-affected class) costs
    # O(batch) instead of O(region fixpoint).  The predicate is exact for
    # skipping (empty region == repair is a no-op), so gated and ungated
    # runs are bit-identical; gating only changes RepairStats (TIER_SKIP).
    repair_gate: bool = True

    def __post_init__(self):
        assert self.edge_capacity & (self.edge_capacity - 1) == 0, (
            "edge_capacity must be a power of two")
        # normalize so configs differing only in registry spelling hash the
        # same (GraphConfig is a static jit argument)
        object.__setattr__(self, "region_edge_buckets",
                           tuple(sorted(set(int(b) for b in
                                            self.region_edge_buckets))))
        assert all(b > 0 for b in self.region_edge_buckets), (
            "region_edge_buckets must be positive")
        assert self.region_vertex_capacity >= 0
        assert self.sparse_impl in ("auto", "pallas", "pallas_interpret",
                                    "xla"), self.sparse_impl


class GraphState(NamedTuple):
    """The dynamic SCC-Graph.  A pytree of arrays; capacities are static."""

    v_alive: jax.Array  # bool[NV]   vertex slot is live
    ccid: jax.Array  # int32[NV]  canonical SCC label (min id in SCC); NV if dead
    edges: et.EdgeTable  # hash table over (src, dst)
    n_ccs: jax.Array  # int32[]    live SCC count  (paper: ``ccCount``)
    gen: jax.Array  # int32[]    bumped whenever the SCC partition changes
    overflow: jax.Array  # int32[]    # of table-op failures (host must grow)


def empty(cfg: GraphConfig) -> GraphState:
    nv = cfg.n_vertices
    return GraphState(
        v_alive=jnp.zeros((nv,), jnp.bool_),
        ccid=jnp.full((nv,), nv, jnp.int32),
        edges=et.empty(cfg.edge_capacity),
        n_ccs=jnp.zeros((), jnp.int32),
        gen=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), jnp.int32),
    )


def from_arrays(cfg: GraphConfig, src, dst, n_active_vertices=None) -> GraphState:
    """Bulk-load a static graph (host path, used by tests/benches).

    ``ccid`` is *not* computed here; call :func:`repro.core.scc.recompute` on
    the result (or go through ``dynamic.apply_batch``).
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    state = empty(cfg)
    nv = cfg.n_vertices
    if n_active_vertices is None:
        n_active_vertices = nv
    v_alive = (jnp.arange(nv) < n_active_vertices)
    # overflow = keys the table itself reports dropped on probe exhaustion
    # (duplicates in the input are found / deduped, so they do not count).
    table, _, failed = et.insert(state.edges, src, dst, cfg.max_probes,
                                 impl=cfg.sparse_impl)
    state = state._replace(
        v_alive=v_alive,
        edges=table,
        overflow=state.overflow + jnp.sum(failed).astype(jnp.int32),
    )
    return state


def all_singletons(cfg: GraphConfig) -> GraphState:
    """Every vertex slot live, each its own SCC, no edges -- the standard
    boot state for stream drivers (edge ops land immediately)."""
    nv = cfg.n_vertices
    return recount_ccs(empty(cfg)._replace(
        v_alive=jnp.ones((nv,), jnp.bool_),
        ccid=jnp.arange(nv, dtype=jnp.int32)))


def edge_coo(state: GraphState):
    """(src, dst, live_mask) view of the edge table, for segment-op sweeps."""
    t = state.edges
    live = t.state == et.LIVE
    return t.src, t.dst, live


def live_edge_count(state: GraphState) -> jax.Array:
    return jnp.sum(state.edges.state == et.LIVE).astype(jnp.int32)


def live_vertex_count(state: GraphState) -> jax.Array:
    return jnp.sum(state.v_alive).astype(jnp.int32)


def recount_ccs(state: GraphState) -> GraphState:
    """n_ccs = #representatives (v alive with ccid[v] == v).

    Canonical labels are the min id of the SCC, which is itself a member, so
    counting fixed points of the label map counts components exactly.
    """
    nv = state.ccid.shape[0]
    reps = state.v_alive & (state.ccid == jnp.arange(nv, dtype=jnp.int32))
    return state._replace(n_ccs=jnp.sum(reps).astype(jnp.int32))
