"""Vmapped multi-tenant engine: many small graphs, one compiled step.

The paper serves ONE shared-memory graph; production traffic is thousands
of independent session graphs.  The concurrent-graph line of work this
repo follows gets its throughput by composing many small linearizable
structures under one object -- the JAX analogue is stacking per-tenant
:class:`~repro.core.graph_state.GraphState` pytrees along a leading
*tenant axis* and running the already-compiled 5-phase scan step under
``jax.vmap``: T tenants' same-shape super-chunks cost one dispatch and
one deferred host transfer instead of T.

Design rules (all load-bearing for the differential oracle test):

* **Same scheduler, same gens.**  Each tenant's chunk is cut by the very
  same :class:`~repro.launch.stream.BucketedScheduler` plan and
  scan-length registry a single-tenant :class:`SCCService` would use, so
  the per-tenant generation trajectory (one bump per plan entry) is
  bit-identical to the oracle's.  Idle tenants are never stepped.
* **Per-lane fault isolation.**  Overflow and ``RepairStats`` outputs
  stay per-lane.  A lane that overflows anywhere in its chunk is
  discarded wholesale and the chunk replays *solo* through a throwaway
  ``SCCService`` seeded with the tenant's pre-state and the engine's
  decision knobs -- literally the oracle's own grow-and-replay code, so
  growth escalation, replay gens, and table layout match the
  single-tenant service decision-for-decision.  Other lanes commit from
  the shared dispatch untouched.
* **Capacity groups.**  ``vmap`` needs one static config per dispatch,
  so tenants are grouped by their current :class:`GraphConfig`; a grown
  tenant migrates to the group of its new capacity.  Per-tenant edge
  capacities therefore always come from the shared growth ladder
  (``boot capacity x grow_factor^k``) -- the bucket-registry discipline
  applied to the tenant axis.
* **Bounded compiles.**  Tenant batches are padded to a small registry
  (``tenant_batches``) exactly like op chunks are padded to ``buckets``;
  compiled update entries are keyed ``(tenant_batch, scan_len, bucket,
  cfg)`` and the registry asserts the
  ``len(tenant_batches) x len(scan_lengths) x len(buckets)``-per-config
  bound on every insertion.
* **Compaction cadence.**  The oracle checks tombstone pressure after
  every chunk; the engine replicates that with ONE vmapped
  ``fill_stats`` over the flushed lanes (amortized into the flush's
  single host sync) and compacts over-threshold lanes through the same
  throwaway-service path.
* **Reads pin a published view.**  Every mutation ends by publishing an
  immutable ``tid -> (stack, lane, gen, cfg)`` map; :meth:`pin` reads it
  without the engine lock, so a read never waits behind a wave.

Engine parity with the oracle assumes ``proactive_grow=False`` (the
service default): proactive growth is a heuristic that changes *when*
capacity is minted, and the engine intentionally keeps the reactive
grow-and-replay backstop as the only growth path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import dynamic, edge_table as et, graph_state as gs
from repro.core.service import SCCService, _ids_in_range
from repro.launch.stream import BucketedScheduler

__all__ = ["TenantEngine"]


# --------------------------------------------------------------- jit entries

@partial(jax.jit, static_argnames=("cfg",))
def _vmapped_scan(states, ops, cfg):
    """vmap of the fused K-step scan over a leading tenant axis.

    states: GraphState pytree with leading [T] axis; ops: OpBatch with
    int32[T, K, B] leaves.  Returns (states', ok bool[T, K, B],
    ovf int32[T, K], RepairStats int32[T, K]) -- overflow and repair
    telemetry stay per-lane, which is what keeps one tenant's doom from
    touching another's commit.
    """
    return jax.vmap(
        lambda s, o: dynamic._apply_batch_scan_impl(s, o, cfg))(states, ops)


@jax.jit
def _vmapped_fill_stats(tables):
    """(live, tomb) int32[T] over a stacked edge-table pytree."""
    return jax.vmap(et.fill_stats)(tables)


@jax.jit
def _vmapped_same_scc(states, u, v):
    """bool[T, Q]: per-tenant checkSCC batches in one dispatch."""
    from repro.core import community
    return jax.vmap(community.check_scc)(states, u, v)


@jax.jit
def _vmapped_community_of(states, u):
    """int32[T, Q]: per-tenant blongsToCommunity in one dispatch."""
    from repro.core import community
    return jax.vmap(community.belongs_to_community)(states, u)


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# Lane gathers and scatters run as ONE program over the whole pytree:
# per-leaf eager ops would dispatch a program per leaf, and each dispatch
# waits again for the interpreter lock that busy reader threads hold.
# Indices are traced, so there is one compile per shape, not per lane.

@jax.jit
def _take(tree, idx):
    """Rows ``idx`` (a scalar: one lane) of every leaf."""
    return jax.tree.map(lambda a: a[idx], tree)


@jax.jit
def _put(tree, idx, src, rows):
    """``tree`` with rows ``idx`` set to ``src``'s rows ``rows``; an
    index past the end is dropped (a padding row)."""
    return jax.tree.map(lambda a, b: a.at[idx].set(b[rows], mode="drop"),
                        tree, src)


def _rows(idx) -> np.ndarray:
    return np.asarray(idx, np.int32)


def _lane(tree, i: int):
    return _take(tree, np.int32(i))


def _set_lane(tree, i: int, lane):
    return jax.tree.map(lambda a, x: a.at[i].set(x), tree, lane)


# ------------------------------------------------------------- bookkeeping

@dataclasses.dataclass
class _Tenant:
    tid: str
    cfg: gs.GraphConfig        # current capacity group key
    lane: int                  # lane index inside the group's stack
    gen: int                   # host-tracked committed generation
    applied_chunks: int = 0
    fallback_chunks: int = 0
    grow_count: int = 0
    replayed_ops: int = 0
    compaction_count: int = 0


class _View(NamedTuple):
    """One tenant's entry in the engine's published read view."""
    states: object             # its group's committed stack
    lane: int
    gen: int
    cfg: gs.GraphConfig


class _Group:
    """One capacity class: a stacked GraphState plus its lane map."""

    def __init__(self, cfg: gs.GraphConfig):
        self.cfg = cfg
        self.states = None            # stacked pytree, leading [L] axis
        self.lanes: List[Optional[str]] = []   # lane -> tid (None = free)

    @property
    def used(self) -> int:
        return sum(1 for t in self.lanes if t is not None)


class _Work:
    """Per-tenant in-flush scratch: piece queue + transfer refs."""

    def __init__(self, tenant: _Tenant, kind, u, v, pieces):
        self.t = tenant
        self.kind, self.u, self.v = kind, u, v
        self.pieces = pieces          # [(slices, np kind/u/v [K, B])]
        self.pos = 0
        self.row = 0                  # row inside the flush's [W] stack
        self.refs = []                # [(slices, xfer index, batch row)]
        self.error: Optional[Exception] = None
        self.ok: Optional[np.ndarray] = None
        self.compacted_solo = False   # fallback path ran _maybe_compact


class TenantEngine:
    """Stacked-lane executor under :class:`MultiTenantService`.

    Holds every tenant's committed state in per-capacity-class stacked
    arrays and applies one chunk per tenant per :meth:`apply_chunks`
    call as rounds of vmapped fused-scan dispatches with ONE host sync
    per capacity group.  Not a public API: the service layer owns
    admission, durability, and the typed client surface.
    """

    def __init__(self, *, buckets: Sequence[int] = (64, 256, 1024),
                 scan_lengths: Sequence[int] = (1, 4, 16),
                 tenant_batches: Sequence[int] = (1, 2, 4, 8),
                 grow_factor: int = 2,
                 max_edge_capacity: int | None = None,
                 compact_tomb_frac: float = 0.25):
        self._sched = BucketedScheduler(buckets)
        self._scan_lengths = tuple(sorted({int(s) for s in scan_lengths}
                                          | {1}))
        self._tenant_batches = tuple(sorted({int(t)
                                             for t in tenant_batches}))
        assert self._tenant_batches and all(t > 0
                                            for t in self._tenant_batches)
        self._grow_factor = grow_factor
        self._max_edge_capacity = max_edge_capacity
        self._compact_tomb_frac = compact_tomb_frac
        self._groups: Dict[gs.GraphConfig, _Group] = {}
        self._tenants: Dict[str, _Tenant] = {}
        # one lock serializes all structural mutation; each mutation
        # ends by publishing the committed read view (_publish), which
        # tenant reads take without the lock
        self._lock = threading.RLock()
        self._commit_cv = threading.Condition(self._lock)
        self._view: Dict[str, _View] = {}
        self._pins = 0
        self._pins_lock = threading.Lock()
        # compiled-entry registries (update entries are the bounded ones;
        # query/fill-stats entries are separately cached, like the
        # service's query shapes)
        self._compiled: set = set()
        self._query_compiled: set = set()
        self._cfgs_minted: set = set()
        self.flush_count = 0
        self.solo_replays = 0
        # repair tiers of the real lane-steps committed (NOP padding rows
        # excluded); "skipped" steps paid the vmapped repair as a select
        self.lane_tier_steps = {name: 0 for name in dynamic.TIER_NAMES}

    @contextlib.contextmanager
    def _locked(self):
        """Hold ``_lock``; the wait for it is an ``engine.lock_wait``
        span."""
        with telemetry.span("engine.lock_wait"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    # ------------------------------------------------------------ registry

    @property
    def compile_count(self) -> int:
        """Distinct vmapped update-step entries dispatched so far."""
        return len(self._compiled)

    @property
    def compile_bound(self) -> int:
        """The asserted ceiling: ``tenant_batches x scan_lengths x
        buckets`` per minted capacity class (mirrors the single-tenant
        ``buckets x (scan_lengths + 1)``-per-config discipline)."""
        return (len(self._tenant_batches) * len(self._scan_lengths)
                * len(self._sched.buckets)
                * max(1, len(self._cfgs_minted)))

    def _register_entry(self, tb: int, k: int, b: int,
                        cfg: gs.GraphConfig):
        key = (tb, k, b, cfg)
        if key in self._compiled:
            return
        self._cfgs_minted.add(cfg)
        self._compiled.add(key)
        assert len(self._compiled) <= self.compile_bound, (
            f"per-flush recompilation detected: {len(self._compiled)} "
            f"vmapped step entries exceed the "
            f"{len(self._tenant_batches)} tenant batches x "
            f"{len(self._scan_lengths)} scan lengths x "
            f"{len(self._sched.buckets)} buckets x "
            f"{len(self._cfgs_minted)} configs bound")

    def _pick_tenant_batch(self, n: int) -> int:
        fits = [t for t in self._tenant_batches if t >= n]
        return fits[0] if fits else self._tenant_batches[-1]

    # ---------------------------------------------------------- tenant CRUD

    def create_tenant(self, tid: str, cfg: gs.GraphConfig,
                      state: gs.GraphState | None = None,
                      gen: int | None = None):
        """Give ``tid`` a lane.  ``state``/``gen`` rehydrate an evicted
        tenant; fresh tenants boot ``gs.empty(cfg)`` at gen 0, exactly a
        fresh ``SCCService(cfg)``."""
        with self._lock:
            assert tid not in self._tenants, f"tenant {tid!r} exists"
            if state is None:
                state = gs.empty(cfg)
            lane = self._add_lane(cfg, state, tid)
            self._tenants[tid] = _Tenant(
                tid=tid, cfg=cfg, lane=lane,
                gen=int(state.gen) if gen is None else int(gen))
            self._publish()

    def remove_tenant(self, tid: str) -> Tuple[gs.GraphState,
                                               gs.GraphConfig, int]:
        """Extract ``tid``'s lane and compact it out of the stack.
        Returns (state, cfg, gen) so the caller can snapshot or drop."""
        with self._lock:
            t = self._tenants.pop(tid)
            group = self._groups[t.cfg]
            state = _lane(group.states, t.lane)
            group.lanes[t.lane] = None
            self._compact_group(group)
            self._publish()
            return state, t.cfg, t.gen

    def has_tenant(self, tid: str) -> bool:
        with self._lock:
            return tid in self._tenants

    def tenant_ids(self) -> List[str]:
        with self._lock:
            return list(self._tenants)

    def _publish(self):
        """Rebuild the read view from the bookkeeping and publish it with
        one assignment.  Called under ``_lock`` at the end of every
        mutation (``create_tenant``, ``remove_tenant``, ``apply_chunks``
        with its solo replays, compactions and lane moves), never midway,
        so a view only names committed lanes and their generations.
        ``group.states`` is only ever replaced, never written in place,
        so a stack a view names stays a committed snapshot."""
        self._view = {tid: _View(self._groups[t.cfg].states, t.lane,
                                 t.gen, t.cfg)
                      for tid, t in self._tenants.items()}

    def pin(self, tid: str) -> Tuple[gs.GraphState, gs.GraphConfig, int]:
        """``(state, cfg, gen)`` of ``tid``'s last commit, all from one
        published view and without ``_lock``: a read never waits behind
        a wave.  The lane is sliced at once, so the pin keeps no
        reference to the whole stack."""
        with telemetry.span("engine.pin"):
            v = self._view[tid]
            state = _lane(v.states, v.lane)
            with self._pins_lock:
                self._pins += 1
        return state, v.cfg, v.gen

    def tenant_state(self, tid: str) -> gs.GraphState:
        """Committed snapshot of one tenant (lane extraction)."""
        v = self._view[tid]
        return _lane(v.states, v.lane)

    def tenant_cfg(self, tid: str) -> gs.GraphConfig:
        return self._view[tid].cfg

    def tenant_gen(self, tid: str) -> int:
        return self._view[tid].gen

    def wait_for_gen(self, tid: str, gen: int,
                     timeout: float | None = None) -> int:
        """Block until ``tid``'s committed generation reaches ``gen``."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._commit_cv:
            while tid in self._tenants and self._tenants[tid].gen < gen:
                if deadline is None:
                    self._commit_cv.wait()
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._commit_cv.wait(remaining)
            return self._tenants[tid].gen if tid in self._tenants else -1

    def tenant_telemetry(self, tid: str) -> dict:
        with self._lock:
            t = self._tenants[tid]
            return {
                "gen": t.gen,
                "edge_capacity": t.cfg.edge_capacity,
                "applied_chunks": t.applied_chunks,
                "fallback_chunks": t.fallback_chunks,
                "grows": t.grow_count,
                "replayed_ops": t.replayed_ops,
                "compactions": t.compaction_count,
            }

    def occupancy(self) -> dict:
        """Lane-occupancy telemetry per capacity class."""
        with self._lock:
            groups = {g.cfg.edge_capacity: {"lanes": len(g.lanes),
                                            "used": g.used}
                      for g in self._groups.values()}
            lanes = sum(v["lanes"] for v in groups.values())
            used = sum(v["used"] for v in groups.values())
            return {"tenants": len(self._tenants),
                    "lanes": lanes, "used": used,
                    "frac": round(used / lanes, 4) if lanes else 1.0,
                    "by_capacity": groups}

    # ------------------------------------------------------- lane plumbing

    def _add_lane(self, cfg: gs.GraphConfig, state: gs.GraphState,
                  tid: str) -> int:
        group = self._groups.get(cfg)
        if group is None:
            group = self._groups[cfg] = _Group(cfg)
        if group.states is None:
            group.states = _stack([state])
            group.lanes = [tid]
            return 0
        for i, owner in enumerate(group.lanes):
            if owner is None:
                group.states = _set_lane(group.states, i, state)
                group.lanes[i] = tid
                return i
        # full: append exactly one lane.  Tenant creation is control-
        # plane-rare, and keeping groups PACKED is what lets the steady
        # flush run on ``group.states`` in place -- a free tail lane
        # would force a gather/scatter round trip on every wave.
        n = len(group.lanes)
        group.states = jax.tree.map(
            lambda a, x: jnp.concatenate([a, x[None]]),
            group.states, state)
        group.lanes.append(tid)
        return n

    def _compact_group(self, group: _Group):
        """Repack live lanes to the front and shrink the stack -- the
        eviction path's promise that a cold tenant's arrays are actually
        released, not just masked."""
        live = [i for i, t in enumerate(group.lanes) if t is not None]
        if not live:
            del self._groups[group.cfg]
            return
        if live == list(range(len(group.lanes))):
            return
        group.states = _take(group.states, _rows(live))
        for new_lane, old_lane in enumerate(live):
            self._tenants[group.lanes[old_lane]].lane = new_lane
        group.lanes = [group.lanes[i] for i in live]

    def _move_tenant(self, t: _Tenant, new_cfg: gs.GraphConfig,
                     state: gs.GraphState):
        old = self._groups[t.cfg]
        old.lanes[t.lane] = None
        t.cfg = new_cfg
        t.lane = self._add_lane(new_cfg, state, t.tid)
        self._compact_group(old)

    # --------------------------------------------------------- super-chunks

    def _pack_super_chunks(self, kind, u, v):
        """Host-array mirror of ``BucketedScheduler.super_chunks``: the
        identical plan/grouping, but numpy leaves (so cross-tenant
        stacking costs no device round-trip)."""
        lens = self._scan_lengths
        plan = self._sched.plan(kind.shape[0])
        pieces, i = [], 0
        while i < len(plan):
            b = plan[i][1]
            j = i
            while j < len(plan) and plan[j][1] == b:
                j += 1
            while i < j:
                k = max(s for s in lens if s <= j - i)
                group = plan[i:i + k]
                pk = np.full((k, b), dynamic.NOP, np.int32)
                pu = np.zeros((k, b), np.int32)
                pv = np.zeros((k, b), np.int32)
                for r, (sl, _) in enumerate(group):
                    n = sl.stop - sl.start
                    pk[r, :n] = kind[sl]
                    pu[r, :n] = u[sl]
                    pv[r, :n] = v[sl]
                pieces.append(([sl for sl, _ in group], pk, pu, pv))
                i += k
        return pieces

    # -------------------------------------------------------------- updates

    def apply_chunks(self, requests):
        """Apply one chunk per tenant: ``[(tid, kind, u, v), ...]``.

        Returns ``{tid: (ok bool[N], gen int) | Exception}`` -- a failed
        tenant (capacity cap) rolls back all-or-nothing without touching
        any other lane.  A tenant may appear at most once per call; the
        admission queue feeds head-of-line chunks in waves to keep the
        oracle's chunk-boundary compaction cadence.

        Each call emits one ``engine.wave`` event: the real lanes, their
        steps, and how many of those steps each repair tier took.
        """
        out: Dict[str, object] = {}
        tiers = {name: 0 for name in dynamic.TIER_NAMES}
        with self._locked(), telemetry.span("engine.apply",
                                            lanes=len(requests)):
            by_cfg: Dict[gs.GraphConfig, List[_Work]] = {}
            seen = set()
            for tid, kind, u, v in requests:
                assert tid not in seen, f"duplicate chunk for {tid!r}"
                seen.add(tid)
                t = self._tenants[tid]
                kind = np.asarray(kind, np.int32)
                u = np.asarray(u, np.int32)
                v = np.asarray(v, np.int32)
                if kind.shape[0] == 0:
                    out[tid] = (np.zeros(0, bool), t.gen)
                    continue
                w = _Work(t, kind, u, v,
                          self._pack_super_chunks(kind, u, v))
                by_cfg.setdefault(t.cfg, []).append(w)
            for cfg, works in by_cfg.items():
                self._apply_cfg_group(cfg, works, out, tiers)
            self.flush_count += 1
            for name, n in tiers.items():
                self.lane_tier_steps[name] += n
            self._publish()
            self._commit_cv.notify_all()
        telemetry.event("engine.wave", lanes=len(requests),
                        lane_steps=sum(tiers.values()),
                        **{f"tier_{k}": v for k, v in tiers.items()})
        return out

    def _apply_cfg_group(self, cfg: gs.GraphConfig, works: List[_Work],
                         out: dict, tiers: dict):
        # The flush works on ONE [W]-stacked scratch pytree (`cur`) and
        # moves data by whole-batch gather/scatter, never by per-lane
        # slicing: eager per-lane ops (`a[i]`, `a.at[i].set`) cost a
        # dispatch per leaf per tenant and would eat the coalescing win
        # on CPU.  In the steady serving shape -- every lane of the
        # group flushes and the wave matches a registered tenant batch
        # -- `cur` IS `group.states` and a round is exactly one vmapped
        # dispatch with zero data movement.
        group = self._groups[cfg]
        works = sorted(works, key=lambda w: w.t.lane)
        for r, w in enumerate(works):
            w.row = r
        lanes = [w.t.lane for w in works]
        whole = lanes == list(range(len(group.lanes)))
        if whole:
            cur = group.states
        else:
            cur = _take(group.states, _rows(lanes))
        n_rows = len(works)
        xfers: List[tuple] = []       # [(ok [tb,K,B], ovf, tier [tb,K])]
        # --- rounds of vmapped dispatches (async; no host sync) -------
        while True:
            active = [w for w in works if w.pos < len(w.pieces)]
            if not active:
                break
            shapes: Dict[Tuple[int, int], List[_Work]] = {}
            for w in active:
                k, b = w.pieces[w.pos][1].shape
                shapes.setdefault((k, b), []).append(w)
            for (k, b), ws in shapes.items():
                i = 0
                while i < len(ws):
                    tb = self._pick_tenant_batch(len(ws) - i)
                    cur = self._dispatch(cfg, k, b, tb, ws[i:i + tb],
                                         cur, n_rows, xfers)
                    i += tb
            for w in active:
                w.pos += 1
        # --- compaction probe, amortized into the one sync ------------
        live_tomb = _vmapped_fill_stats(cur.edges)
        # --- the flush's single host transfer --------------------------
        with telemetry.span("engine.resolve", lanes=n_rows):
            host_xfers, (_, tomb) = jax.device_get((xfers, live_tomb))
        with telemetry.span("engine.commit", lanes=n_rows):
            # --- per-lane commit / solo replay -------------------------
            fast: List[_Work] = []
            for w in works:
                host_pieces = [tuple(x[r] for x in host_xfers[xi])
                               for _, xi, r in w.refs]
                total_ovf = sum(int(np.sum(ovf))
                                for _, ovf, _ in host_pieces)
                if total_ovf == 0:
                    ok = np.zeros(w.kind.shape[0], bool)
                    steps = 0
                    for (slices, _, _), (ok_kb, _, tier_k) in zip(
                            w.refs, host_pieces):
                        for j, sl in enumerate(slices):
                            ok[sl] = ok_kb[j, :sl.stop - sl.start]
                            tiers[dynamic.TIER_NAMES[int(tier_k[j])]] += 1
                        steps += len(slices)
                    w.ok = ok
                    w.t.gen += steps
                    w.t.applied_chunks += 1
                    fast.append(w)
                else:
                    self._solo_replay(cfg, w, tiers)
            # --- commit fast-path rows back into the stack -------------
            if fast:
                if whole and len(fast) == n_rows:
                    group.states = cur
                else:
                    group.states = _put(
                        group.states, _rows([w.t.lane for w in fast]),
                        cur, _rows([w.row for w in fast]))
            # --- oracle-cadence compaction (post-chunk tombstone check)
            for w, work_tomb in zip(works, tomb):
                if w.error is not None or w.compacted_solo:
                    continue
                if int(work_tomb) > self._compact_tomb_frac * \
                        w.t.cfg.edge_capacity:
                    self._compact_tenant(w.t)
            for w in works:
                out[w.t.tid] = w.error if w.error is not None \
                    else (w.ok, w.t.gen)

    def _dispatch(self, cfg: gs.GraphConfig, k: int, b: int, tb: int,
                  ws: List[_Work], cur, n_rows: int, xfers: list):
        """One vmapped fused-scan step over ≤ tb tenants' current pieces
        (padded to the registered tenant batch with NOP lanes).  Gathers
        the participating rows out of the [W]-stacked ``cur``, scatters
        the results back, and returns the new ``cur``; a full-coverage
        dispatch (every row, exact registered batch) runs on ``cur``
        in place with no gather or scatter at all."""
        self._register_entry(tb, k, b, cfg)
        rows = [w.row for w in ws]
        full = tb == n_rows and rows == list(range(n_rows))
        if full:
            sub = cur
        else:
            sub = _take(cur, _rows(rows + [rows[0]] * (tb - len(rows))))
        pk = np.full((tb, k, b), dynamic.NOP, np.int32)
        pu = np.zeros((tb, k, b), np.int32)
        pv = np.zeros((tb, k, b), np.int32)
        for i, w in enumerate(ws):
            _, wk, wu, wv = w.pieces[w.pos]
            pk[i], pu[i], pv[i] = wk, wu, wv
        ops = dynamic.make_ops(pk, pu, pv)
        with telemetry.span("engine.dispatch", tb=tb, k=k, b=b):
            new_states, ok, ovf, rstats = _vmapped_scan(sub, ops, cfg)
        if full:
            cur = new_states
        else:
            # padding rows scatter past the end of `cur`: dropped
            cur = _put(cur, _rows(rows + [n_rows] * (tb - len(rows))),
                       new_states, _rows(range(tb)))
        xi = len(xfers)
        xfers.append((ok, ovf, rstats.tier))
        for i, w in enumerate(ws):
            w.refs.append((w.pieces[w.pos][0], xi, i))
        return cur

    def _shadow_service(self, cfg: gs.GraphConfig,
                        state: gs.GraphState) -> SCCService:
        """The oracle's own code path, seeded with one tenant's lane:
        every non-fast-path decision (growth escalation, replay,
        compaction) is delegated here so it matches a single-tenant
        service decision-for-decision."""
        return SCCService(cfg, buckets=self._sched.buckets, state=state,
                          grow_factor=self._grow_factor,
                          max_edge_capacity=self._max_edge_capacity,
                          compact_tomb_frac=self._compact_tomb_frac,
                          inflight_window=0, donate=False,
                          scan_lengths=self._scan_lengths,
                          proactive_grow=False)

    def _solo_replay(self, cfg: gs.GraphConfig, w: _Work, tiers: dict):
        """A doomed lane's chunk re-runs alone through grow-and-replay.

        The lane's vmapped outputs are discarded (its stack slot still
        holds the pre-chunk state, since fast-path scatter happens
        after); the shadow service replays the WHOLE chunk serially from
        that pre-state -- the same restart the single-tenant fallback
        performs -- then the grown/compacted result re-enters whichever
        capacity group now matches.
        """
        self.solo_replays += 1
        t = w.t
        pre = _lane(self._groups[cfg].states, t.lane)
        svc = self._shadow_service(cfg, pre)
        try:
            ok = svc._apply_chunk(w.kind, w.u, w.v)
        except Exception as e:          # capacity cap: lane unchanged
            t.fallback_chunks += 1
            w.error = e
            return
        t.fallback_chunks += 1
        for name, n in svc.repair_tier_steps.items():
            tiers[name] += n
        t.grow_count += svc.grow_count
        t.replayed_ops += svc.replayed_ops
        t.compaction_count += svc.compaction_count
        t.gen = svc.gen
        t.applied_chunks += 1
        w.ok = ok
        w.compacted_solo = True         # shadow ran _maybe_compact
        if svc.cfg != cfg:
            self._move_tenant(t, svc.cfg, svc.state)
        else:
            group = self._groups[cfg]
            group.states = _set_lane(group.states, t.lane, svc.state)

    def _compact_tenant(self, t: _Tenant):
        """Post-chunk tombstone compaction, shadow-service style; a
        compaction that escalates capacity migrates the tenant."""
        group = self._groups[t.cfg]
        svc = self._shadow_service(t.cfg, _lane(group.states, t.lane))
        svc._maybe_compact()
        t.compaction_count += svc.compaction_count
        if svc.cfg != t.cfg:
            t.grow_count += svc.grow_count
            self._move_tenant(t, svc.cfg, svc._state)
        elif svc.compaction_count:
            group.states = _set_lane(group.states, t.lane, svc._state)

    # -------------------------------------------------------------- queries

    def same_scc_many(self, items):
        """Cross-tenant SameSCC: ``[(tid, u, v), ...]`` (arrays per
        tenant) -> ``{tid: (bool[n], gen)}`` -- per-tenant batches padded
        to a shared power-of-two Q and answered in one vmapped gather per
        capacity group, against committed lanes only."""
        return self._query_many(items, with_v=True)

    def community_of_many(self, items):
        """Cross-tenant blongsToCommunity: ``[(tid, u), ...]`` ->
        ``{tid: (int32[n], gen)}`` (sentinel ``n_vertices`` for absent or
        out-of-range ids)."""
        return self._query_many([(tid, u, None) for tid, u in items],
                                with_v=False)

    def _query_many(self, items, *, with_v: bool):
        out = {}
        with self._locked():
            by_cfg: Dict[gs.GraphConfig, list] = {}
            for tid, u, v in items:
                t = self._tenants[tid]
                by_cfg.setdefault(t.cfg, []).append(
                    (t, np.asarray(u, np.int64),
                     None if v is None else np.asarray(v, np.int64)))
            for cfg, rows in by_cfg.items():
                group = self._groups[cfg]
                qmax = max(max(r[1].shape[0], 1) for r in rows)
                q = 1 << (qmax - 1).bit_length()
                tb = self._pick_tenant_batch(len(rows))
                self._query_compiled.add(
                    ("same_scc" if with_v else "community_of",
                     tb, q, cfg))
                i = 0
                while i < len(rows):
                    sub = rows[i:i + tb]
                    i += tb
                    lanes = [r[0].lane for r in sub]
                    while len(lanes) < tb:
                        lanes.append(lanes[0])
                    states = _take(group.states, _rows(lanes))
                    pu = np.zeros((tb, q), np.int32)
                    pv = np.zeros((tb, q), np.int32)
                    for r, (t, uu, vv) in enumerate(sub):
                        # clip to int32 range; true range masking below
                        pu[r, :uu.shape[0]] = np.clip(uu, -1,
                                                      cfg.n_vertices)
                        if vv is not None:
                            pv[r, :vv.shape[0]] = np.clip(
                                vv, -1, cfg.n_vertices)
                    if with_v:
                        res = np.asarray(_vmapped_same_scc(
                            states, jnp.asarray(pu), jnp.asarray(pv)))
                    else:
                        res = np.asarray(_vmapped_community_of(
                            states, jnp.asarray(pu)))
                    for r, (t, uu, vv) in enumerate(sub):
                        n = uu.shape[0]
                        vals = res[r, :n]
                        if with_v:
                            vals = vals & _ids_in_range(uu, cfg.n_vertices) \
                                & _ids_in_range(vv, cfg.n_vertices)
                        else:
                            vals = vals.copy()
                            vals[~_ids_in_range(uu, cfg.n_vertices)] = \
                                cfg.n_vertices
                        out[t.tid] = (vals, t.gen)
        return out

    # ---------------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._lock:
            return {
                "tenants": len(self._tenants),
                "flushes": self.flush_count,
                "solo_replays": self.solo_replays,
                "lane_tier_steps": dict(self.lane_tier_steps),
                "compile_count": self.compile_count,
                "compile_bound": self.compile_bound,
                "query_shapes": len(self._query_compiled),
                "pins": self._pins,
                "occupancy": self.occupancy(),
                "tenant_batches": list(self._tenant_batches),
            }
