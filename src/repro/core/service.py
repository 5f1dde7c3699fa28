"""Streaming SCC service: the paper's on-line system around ``apply_batch``.

The paper (arXiv:1804.01276) runs SMSCC as a *service*: a fixed thread pool
applies an unbounded stream of graph updates while readers issue wait-free
SameSCC/reachability queries.  ``dynamic.apply_batch`` is our compiled
analogue of one scheduling quantum; this module supplies the host-side
machinery that turns it into a long-running service:

grow-and-replay
    ``apply_batch`` can only report probe-bound overflow (the
    ``GraphState.overflow`` counter); it cannot grow the hash table because
    its shapes are static.  The service watches the per-step overflow
    *delta*, identifies exactly the AddEdge lanes whose key is missing from
    the post-step table, rehashes the table into a geometrically larger
    capacity (``edge_table.rehash``, jitted once per target capacity), and
    replays the failed lanes.  Invariant: **no accepted edge is ever lost**
    -- after ``apply()`` returns, the table contains every edge a
    sequential unbounded-table execution would contain, and the reported
    per-op results match that sequential history.

bucketed scheduling
    An unbounded stream has unbounded batch lengths; jit would recompile
    per length.  The scheduler (:class:`repro.launch.stream.BucketedScheduler`)
    cuts the stream into a small fixed set of padded shapes (NOP padding),
    so total XLA compilations are bounded by ``len(buckets) x #capacities``
    regardless of stream length.  ``compile_count`` tracks this bound.

snapshot queries
    States are immutable pytrees; the service keeps a pointer to the last
    *committed* state (updated only after a chunk fully applies, including
    any replay).  Readers therefore always see a consistent generation --
    the batched analogue of the paper's wait-free reader guarantee -- and
    every query result is stamped with the generation it was computed at.

concurrent-reader pipeline + fused scan engine
    The updater path no longer forces a device->host sync (or even a
    dispatch) per step: runs of same-bucket batches are stacked into
    *super-chunks* from a geometric scan-length registry and dispatched
    through ``dynamic.apply_batch_scan_inflight`` -- one fused
    ``lax.scan`` program per (scan length, bucket, cfg), one dispatch
    and one deferred ``jax.device_get`` of the stacked
    (ok, overflow, RepairStats) tuple per super-chunk, optional buffer
    donation between super-chunks -- resolved behind a bounded in-flight
    window.  A chunk whose window stays overflow-free commits in one
    shot; overflow aborts the fast path and the serial grow-and-replay
    path replays from the first chunk of the offending super-chunk
    (resolved-clean prefix kept) when its input state is still alive,
    else from the untouched committed snapshot -- results are
    bit-identical every way.  The committed snapshot is double-buffered
    against donation (the pipeline steps off a private device copy),
    which is what lets a :class:`repro.core.broker.QueryBroker` serve
    readers from ``service.state`` while the next update step is still
    executing.  With ``proactive_grow`` the service additionally
    rehashes ahead of a chunk whose deduped AddEdge lanes cannot fit,
    keeping growth waves off the dispatch critical path.  See
    ``docs/ARCHITECTURE.md`` for the full request lifecycle and
    ``docs/SERVICE_API.md`` for the consistency contract.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from functools import partial
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import community, dynamic, edge_table as et
from repro.core import graph_state as gs
from repro.fault import errors as fault_errors

_MAX_GROW_ROUNDS = 16


class Snapshot(NamedTuple):
    """A query result stamped with the SCC-partition generation it saw."""
    value: np.ndarray
    gen: int


@partial(jax.jit, static_argnames=("new_capacity", "max_probes", "impl"))
def _rehash(table: et.EdgeTable, new_capacity: int, max_probes: int,
            impl: str = "xla"):
    return et.rehash(table, new_capacity, max_probes, impl=impl)


@partial(jax.jit, static_argnames=("max_inner", "impl"))
def _reachable_batch(state: gs.GraphState, u, v, max_inner: int,
                     impl: str = "xla"):
    """bool[Q]: u[i] ⇝ v[i] over live edges (u==v and alive counts)."""
    nv = state.ccid.shape[0]
    uu = jnp.clip(u, 0, nv - 1)
    vv = jnp.clip(v, 0, nv - 1)
    src, dst, live = gs.edge_coo(state)
    seeds = jnp.zeros((u.shape[0], nv), jnp.bool_).at[
        jnp.arange(u.shape[0]), uu].set(True)
    from repro.core import reach
    reached, _ = reach.multi_forward_reach(src, dst, live, seeds,
                                           state.v_alive, max_inner,
                                           impl=impl)
    ok = state.v_alive[uu] & state.v_alive[vv]
    return ok & reached[jnp.arange(u.shape[0]), vv]


@jax.jit
def _members(state: gs.GraphState, u):
    """bool[NV]: vertices in u's SCC (empty mask when u is dead)."""
    nv = state.ccid.shape[0]
    uu = jnp.clip(u, 0, nv - 1)
    lab = jnp.where(state.v_alive[uu], state.ccid[uu], nv)
    return state.v_alive & (state.ccid == lab)


@jax.jit
def _members_batch(state: gs.GraphState, u):
    """bool[Q, NV]: row i is the membership mask of u[i]'s SCC."""
    nv = state.ccid.shape[0]
    uu = jnp.clip(u, 0, nv - 1)
    lab = jnp.where(state.v_alive[uu], state.ccid[uu], nv)
    return state.v_alive[None, :] & (state.ccid[None, :] == lab[:, None])


def _ids_in_range(ids, nv: int) -> np.ndarray:
    ids = np.asarray(ids)
    return (ids >= 0) & (ids < nv)


# Snapshot-query primitives shared by SCCService and QueryBroker: each
# answers against an explicit pinned state (NOT the service's live pointer),
# which is what lets the broker serve a whole coalesced batch from one
# consistent generation.

def same_scc_on(state: gs.GraphState, cfg: gs.GraphConfig, u, v
                ) -> np.ndarray:
    """bool[Q]: SameSCC on a pinned snapshot; out-of-range ids answer
    False, never alias a clipped vertex."""
    res = community.check_scc(state, jnp.asarray(u, jnp.int32),
                              jnp.asarray(v, jnp.int32))
    return np.asarray(res) & _ids_in_range(u, cfg.n_vertices) \
        & _ids_in_range(v, cfg.n_vertices)


def reachable_on(state: gs.GraphState, cfg: gs.GraphConfig, u, v
                 ) -> np.ndarray:
    """bool[Q]: u[i] ⇝ v[i] on a pinned snapshot."""
    res = _reachable_batch(state, jnp.asarray(u, jnp.int32),
                           jnp.asarray(v, jnp.int32), cfg.max_inner,
                           impl=cfg.sparse_impl)
    return np.asarray(res) & _ids_in_range(u, cfg.n_vertices) \
        & _ids_in_range(v, cfg.n_vertices)


def members_on(state: gs.GraphState, cfg: gs.GraphConfig, u) -> np.ndarray:
    """bool[Q, NV]: SCC membership masks on a pinned snapshot; rows of
    out-of-range ids are all-False."""
    res = np.array(_members_batch(state, jnp.asarray(u, jnp.int32)))
    res[~_ids_in_range(u, cfg.n_vertices)] = False
    return res


def community_of_on(state: gs.GraphState, cfg: gs.GraphConfig, u
                    ) -> np.ndarray:
    """int32[Q]: community (SCC) id on a pinned snapshot; out-of-range or
    dead ids answer the sentinel ``n_vertices``, never alias a clipped
    vertex (paper blongsToCommunity contract)."""
    lab = np.array(community.belongs_to_community(
        state, jnp.asarray(u, jnp.int32)))
    lab[~_ids_in_range(u, cfg.n_vertices)] = cfg.n_vertices
    return lab


def community_sizes_on(state: gs.GraphState, cfg: gs.GraphConfig
                       ) -> np.ndarray:
    """int32[NV]: community-size histogram (indexed by representative id)
    on a pinned snapshot."""
    return np.asarray(community.community_sizes(state))


class SCCService:
    """Host-side streaming wrapper: grow-and-replay + bucketed scheduling +
    generation-stamped snapshot queries over ``dynamic.apply_batch``."""

    def __init__(self, cfg: gs.GraphConfig,
                 buckets: Sequence[int] = (64, 256, 1024),
                 state: gs.GraphState | None = None,
                 grow_factor: int = 2,
                 max_edge_capacity: int | None = None,
                 compact_tomb_frac: float = 0.25,
                 inflight_window: int = 8,
                 donate: bool | None = None,
                 scan_lengths: Sequence[int] = (1, 4, 16),
                 proactive_grow: bool = False):
        from repro.launch.stream import BucketedScheduler
        self._cfg = cfg
        self._state = gs.empty(cfg) if state is None else state
        self._sched = BucketedScheduler(buckets)
        self._grow_factor = grow_factor
        self._max_edge_capacity = max_edge_capacity
        self._compact_tomb_frac = compact_tomb_frac
        # concurrent pipeline: how many dispatched super-chunks may be in
        # flight before the oldest (ok, ovf, repair) tuple is resolved
        # (0 = serial path only, the pre-pipeline behaviour); donation
        # defaults to on wherever XLA implements it (not CPU).
        self._inflight_window = inflight_window
        self._donate = (jax.default_backend() != "cpu"
                        ) if donate is None else donate
        # scan-length registry (geometric, like the bucket registry): a
        # run of K same-bucket chunks is cut into the largest registered
        # lengths and each group runs as ONE fused lax.scan dispatch with
        # one deferred host transfer.  1 is always in the registry, so no
        # super-chunk is ever padded with NOP steps (generation counting
        # stays identical to the serial path).
        self._scan_lengths = tuple(sorted({int(s) for s in scan_lengths}
                                          | {1}))
        # proactive growth: rehash ahead of a chunk whose AddEdge lanes
        # cannot possibly fit the current table (live + adds > capacity),
        # instead of letting the chunk overflow and replay.  Pure
        # heuristic -- reactive grow-and-replay remains the correctness
        # backstop -- but it keeps growth off the dispatch critical path
        # (no doomed pipelined execution, no serial re-run, fewer step
        # recompiles per growth wave).
        self._proactive_grow = proactive_grow
        # host-side upper bound on the live edge count (true live never
        # exceeds capacity, so this needs no boot sync); tightened
        # whenever a rehash or the proactive probe pays a sync anyway
        self._live_ub = cfg.edge_capacity
        self._committed = self._state
        # update-path serialization (many GraphClient sessions may share
        # one service) + commit notification for consistency-level waits
        self._apply_lock = threading.RLock()
        self._commit_cv = threading.Condition()
        # idempotent re-submit window: per client session, the last
        # applied (seq, ok, gen) -- a retried chunk whose first attempt
        # actually committed (the ack was lost to a fault downstream)
        # returns the recorded result instead of double-applying
        self._session_results: collections.OrderedDict = \
            collections.OrderedDict()
        self._session_window = 4096
        self.deduped_resubmits = 0
        # telemetry
        self._compiled: set = set()
        self.grow_count = 0
        self.proactive_grows = 0
        self.replayed_ops = 0
        self.compaction_count = 0
        self.pipelined_chunks = 0
        self.fallback_chunks = 0
        self.scanned_chunks = 0
        self.scan_dispatches = 0
        # per-step repair-tier telemetry (dynamic.RepairStats resolved
        # lazily, next to the overflow delta; "skipped" counts steps the
        # repair gate proved structure-preserving)
        self.repair_tier_steps = {name: 0 for name in dynamic.TIER_NAMES}
        self.repair_region_v_max = 0
        self.repair_region_e_max = 0
        self.repair_reach_rounds = 0
        self.repair_scc_rounds = 0

    # ------------------------------------------------------------ state ---

    @property
    def cfg(self) -> gs.GraphConfig:
        return self._cfg

    @property
    def state(self) -> gs.GraphState:
        """Latest committed state (safe to checkpoint / query)."""
        return self._committed

    @property
    def gen(self) -> int:
        return int(self._committed.gen)

    def pin(self) -> Tuple[gs.GraphState, gs.GraphConfig, int]:
        """``(state, cfg, gen)`` for one reader flush, from ONE read of
        the committed pointer, with ``gen`` as a host int.  ``cfg`` may
        be read mid-grow relative to the state, but its only mutable
        field (``edge_capacity``) never enters a query."""
        st = self._committed
        return st, self._cfg, int(st.gen)

    @property
    def compile_count(self) -> int:
        """Distinct (step-path, batch-shape, graph-config) entries stepped
        so far -- an upper bound on *update-step* compiles.  Per graph
        config the entries are: one fused-scan program per (scan length
        > 1, bucket) pair, one single-step pipelined program per bucket
        (super-chunks of length 1 reuse it), and one serial
        grow-and-replay program per bucket -- the bound is
        ``len(buckets) x (len(scan_lengths) + 1)`` per config.  The
        serial entries only ever materialize on chunks that overflowed;
        on non-donating backends the single-step pipelined and serial
        paths actually share one jit entry, so real compiles come in
        under the bound.  Repair tiers and the repair gate never mint
        entries: both are runtime branches inside the one compiled step
        program.  Table rehashes (one per target capacity) and query
        batches (one per query shape) have their own, separately-cached
        jit entries not counted here."""
        return len(self._compiled)

    # ---------------------------------------------------------- updates ---

    def _apply_ops(self, kind, u, v, *, session=None, seq=None):
        """GraphClient entry: apply a chunk and report the commit gen it
        is covered by, atomically w.r.t. concurrent client sessions.

        ``(session, seq)`` is the client's idempotency key: a re-submit
        of the session's last applied sequence number returns the
        recorded (ok, gen) without re-applying -- the retry safety net
        when an ack is lost to a downstream fault.  The window is one
        chunk deep per session, which is exactly what a serial retrying
        client needs (it never has two chunks in flight)."""
        with self._apply_lock:
            if session is not None:
                hit = self._session_results.get(session)
                if hit is not None and hit[0] == seq:
                    self.deduped_resubmits += 1
                    return hit[1], hit[2]
            ok = self._apply_chunk(kind, u, v)
            if session is not None:
                self._session_results[session] = (seq, ok, self.gen)
                self._session_results.move_to_end(session)
                while len(self._session_results) > self._session_window:
                    self._session_results.popitem(last=False)
            return ok, self.gen

    _STAT_ATTRS = ("grow_count", "proactive_grows", "replayed_ops",
                   "compaction_count", "pipelined_chunks",
                   "fallback_chunks", "scanned_chunks", "scan_dispatches",
                   "repair_region_v_max", "repair_region_e_max",
                   "repair_reach_rounds", "repair_scc_rounds")

    def _stats_snapshot(self) -> dict:
        snap = {a: getattr(self, a) for a in self._STAT_ATTRS}
        snap["_compiled"] = set(self._compiled)
        snap["repair_tier_steps"] = dict(self.repair_tier_steps)
        return snap

    def _stats_restore(self, snap: dict):
        for a in self._STAT_ATTRS:
            setattr(self, a, snap[a])
        self._compiled = snap["_compiled"]
        self.repair_tier_steps = snap["repair_tier_steps"]

    def _apply_chunk(self, kind, u, v) -> np.ndarray:
        """Apply a variable-length op stream chunk; returns ok: bool[N].

        The chunk is cut into padded bucket batches; each batch goes
        through grow-and-replay so no AddEdge is ever dropped.  Results
        match the documented per-batch linearization applied bucket by
        bucket.

        Fast path: the bucket batches are grouped into scan-length
        super-chunks and dispatched as fused in-flight ``lax.scan`` steps
        (one dispatch and one deferred host transfer per super-chunk;
        buffers donated super-chunk-to-super-chunk when the backend
        supports it) and the chunk commits after the deferred overflow
        checks drain clean.  Overflow anywhere aborts the fast path and
        the chunk re-runs on the serial grow-and-replay path, replaying
        only from the first chunk of the offending super-chunk when its
        input state is still alive (always, unless donation consumed it
        -- then from the untouched committed snapshot).  Every path
        computes identical results, so callers cannot observe which ran.
        """
        kind = np.asarray(kind, np.int32)
        u = np.asarray(u, np.int32)
        v = np.asarray(v, np.int32)
        with self._apply_lock, telemetry.span("service.apply",
                                              ops=kind.shape[0]):
            entry_state, entry_cfg = self._state, self._cfg
            entry_stats = self._stats_snapshot()
            try:
                if self._proactive_grow:
                    self._maybe_grow_proactive(kind, u, v)
                # the chunk's base: after any proactive growth (a replay
                # from scratch must not undo the rehash, only the ops)
                base_state, base_cfg = self._state, self._cfg
                ok, replay = None, (0, None)
                if self._inflight_window > 0:
                    ok, replay = self._apply_pipelined(kind, u, v)
                if replay is not None:  # overflow (or pipeline off)
                    start, restore = replay
                    self.fallback_chunks += 1
                    if restore is None:  # donated / pipeline off: restart
                        start = 0
                        self._state, self._cfg = base_state, base_cfg
                        ok = np.zeros(kind.shape[0], bool)
                    else:  # prefix super-chunks stay applied
                        self._state = restore
                    with telemetry.span("service.replay",
                                        ops=kind.shape[0] - start):
                        for sl, ops in self._sched.chunks(
                                kind[start:], u[start:], v[start:]):
                            n_real = sl.stop - sl.start
                            ok[start + sl.start:
                               start + sl.start + n_real] = \
                                self._apply_padded(ops)[:n_real]
                else:
                    self.pipelined_chunks += 1
                # inserts can only add this chunk's AddEdge lanes; keep
                # the host-side live bound current without a sync
                self._live_ub = min(
                    self._cfg.edge_capacity,
                    self._live_ub + int(np.sum(kind == dynamic.ADD_EDGE)))
                self._maybe_compact()
            except Exception:
                # all-or-nothing chunk: never let a half-applied batch, a
                # cfg that no longer matches the table, or telemetry for
                # aborted work leak into the next chunk's commit
                self._state, self._cfg = entry_state, entry_cfg
                self._stats_restore(entry_stats)
                raise
            with self._commit_cv:
                self._committed = self._state
                self._commit_cv.notify_all()
        return ok

    def wait_for_gen(self, gen: int, timeout: float | None = None) -> int:
        """Block until the committed generation reaches ``gen`` (the
        consistency-level hook used by AT_LEAST / READ_YOUR_WRITES reads);
        returns the committed generation at wake-up.  Every commit
        notifies under ``_commit_cv`` (the pointer is only ever advanced
        inside it), so a plain wait cannot miss a wakeup."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._commit_cv:
            while self.gen < gen:
                if deadline is None:
                    self._commit_cv.wait()
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._commit_cv.wait(remaining)
            return self.gen

    def _maybe_grow_proactive(self, kind: np.ndarray, u: np.ndarray,
                              v: np.ndarray):
        """Grow ahead of a chunk whose AddEdge lanes cannot all fit.

        Heuristic trigger, exact effect.  The chunk's AddEdge keys are
        deduped and probed against the table (re-adds of live edges can
        never take a slot), so a steady-state re-add chunk never
        triggers a spurious rehash; the chunk's remove lanes are
        subtracted as a crude proxy for same-chunk frees (edge removals
        and vertex-kill incident trims land *before* the adds in each
        batch's phase order, so churn-heavy mixes keep fitting the
        table).  The cheap host-side live upper bound short-circuits
        the device probe in the common no-pressure case.  The effect is
        exact (rehash preserves every live edge) and a missed or
        under-prediction is harmless: reactive grow-and-replay still
        backstops any probe-bound overflow.
        """
        adds = kind == dynamic.ADD_EDGE
        n_add_raw = int(np.sum(adds))
        if n_add_raw == 0:
            return
        if self._live_ub + n_add_raw <= self._cfg.edge_capacity:
            return  # cannot overflow even if every add is new: no sync
        live = int(et.fill_stats(self._state.edges)[0])
        self._live_ub = live  # refresh the bound while we paid the sync
        n_rem = int(np.sum((kind == dynamic.REM_EDGE)
                           | (kind == dynamic.REM_VERTEX)))
        keys = np.unique(np.stack([u[adds], v[adds]], axis=1), axis=0)
        if live + keys.shape[0] - n_rem <= self._cfg.edge_capacity:
            return  # crude estimate fits: skip the table probe
        # the crude estimate indicates growth: confirm by probing the
        # deduped keys against the table, so re-adds of live edges never
        # trigger a rehash.  Padded to a power-of-two lane count so the
        # probe's cached XLA shapes stay bounded per capacity.
        n_keys = keys.shape[0]
        n_pad = 1 << max(0, (n_keys - 1).bit_length())
        ku = np.full(n_pad, -1, np.int32)
        kv = np.full(n_pad, -1, np.int32)
        ku[:n_keys] = keys[:, 0]
        kv[:n_keys] = keys[:, 1]
        found, _ = et.lookup(self._state.edges, jnp.asarray(ku),
                             jnp.asarray(kv), self._cfg.max_probes,
                             impl=self._cfg.sparse_impl)
        n_new = int(np.sum(~np.asarray(found)[:n_keys]))
        predicted = live + n_new - n_rem
        if predicted <= self._cfg.edge_capacity:
            return
        cap = self._cfg.edge_capacity
        while cap < 2 * predicted:  # land at <= 50% load
            cap *= self._grow_factor
        if self._max_edge_capacity:
            while cap > self._max_edge_capacity:
                cap //= self._grow_factor
            if cap <= self._cfg.edge_capacity:
                return  # capped out: let the reactive path report it
        self.grow(cap)
        self.proactive_grows += 1

    class _InFlight(NamedTuple):
        """One dispatched super-chunk awaiting its deferred resolution."""
        slices: list          # chunk slices covered, in scan order
        ok: object            # bool[K, B] (or bool[B] when K == 1) device
        ovf: object           # int32[K] (or int32[]) device
        rstats: object        # RepairStats, int32[K] (or []) leaves
        entry: object         # input GraphState; None when donated away
        scanned: bool         # ran through the fused scan program

    def _apply_pipelined(self, kind, u, v
                         ) -> tuple:
        """Dispatch the whole chunk as fused super-chunks, no per-batch
        host syncs.

        The bucket batches are grouped by the scan-length registry; each
        group of K > 1 runs as ONE ``dynamic.apply_batch_scan`` dispatch
        (singletons reuse the single-step in-flight entry).  A
        super-chunk's (ok, overflow, repair) outputs are resolved in ONE
        ``jax.device_get`` only once ``inflight_window`` newer
        super-chunks have been dispatched (or at drain).

        Returns ``(ok, replay)``: ``replay`` is ``None`` when the whole
        chunk applied cleanly (``self._state`` advanced, ``ok``
        complete), else ``(start, state)`` -- the caller must re-run ops
        from chunk offset ``start`` on the serial grow-and-replay path.
        ``state`` is the offending super-chunk's input state (its prefix
        is already applied and ``ok[:start]`` filled), or ``None`` when
        donation consumed it, in which case the whole chunk must restart
        (``start`` is then ignored).

        When donating, the pipeline steps off a private device copy of
        the current state (double buffering): readers keep a valid
        ``self._committed`` while XLA reuses the pipeline's own buffers
        super-chunk-to-super-chunk.  On non-donating backends each
        in-flight record keeps its input state alive (at most
        ``inflight_window + 1`` states) -- the partial-replay anchor.
        """
        state = self._state
        if self._donate:
            state = jax.tree_util.tree_map(jnp.copy, state)
        ok = np.zeros(kind.shape[0], bool)
        pending: collections.deque = collections.deque()
        # telemetry of resolved-clean super-chunks, committed only for
        # work that stays applied: recording eagerly would double-count
        # the prefix when a donated pipeline aborts and the whole chunk
        # replays through _apply_padded (which records its own steps)
        repair_rows: list = []
        scanned = 0

        def resolve_oldest():
            """One host transfer for the oldest super-chunk; returns the
            record iff it overflowed (else applies its ok rows/stats)."""
            nonlocal scanned
            rec = pending.popleft()
            with telemetry.span("service.resolve", steps=len(rec.slices)):
                ok_h, ovf_h, r_h = jax.device_get((rec.ok, rec.ovf,
                                                   rec.rstats))
            if np.any(ovf_h):
                return rec
            for sl, row in zip(rec.slices, np.atleast_2d(ok_h)):
                ok[sl] = row[: sl.stop - sl.start]
            repair_rows.extend(zip(*map(np.atleast_1d, r_h)))
            if rec.scanned:
                scanned += len(rec.slices)
            return None

        def commit_telemetry():
            for row in repair_rows:
                self._record_repair(*row)
            self.scanned_chunks += scanned

        bad = None
        for slices, ops in self._sched.super_chunks(kind, u, v,
                                                    self._scan_lengths):
            k, b = len(slices), int(ops.kind.shape[1])
            entry = None if self._donate else state
            with telemetry.span("service.dispatch", k=k, b=b):
                if k == 1:
                    self._compiled.add(("pipelined", b, self._cfg))
                    state, ok_dev, ovf, rstats = \
                        dynamic.apply_batch_inflight(
                            state, dynamic.OpBatch(ops.kind[0], ops.u[0],
                                                   ops.v[0]),
                            self._cfg, donate=self._donate)
                else:
                    self._compiled.add(("scan", k, b, self._cfg))
                    state, ok_dev, ovf, rstats = \
                        dynamic.apply_batch_scan_inflight(
                            state, ops, self._cfg, donate=self._donate)
                    self.scan_dispatches += 1
            pending.append(self._InFlight(slices, ok_dev, ovf, rstats,
                                          entry, k > 1))
            if len(pending) > self._inflight_window:
                bad = resolve_oldest()
                if bad is not None:
                    break
        while bad is None and pending:
            bad = resolve_oldest()
        if bad is not None:
            if bad.entry is not None:  # prefix stays applied: record it
                commit_telemetry()
            return ok, (bad.slices[0].start, bad.entry)
        self._state = state
        commit_telemetry()
        return ok, None

    def _record_repair(self, tier, region_v, region_e, reach_rounds,
                       scc_rounds):
        """Count one resolved step's ``RepairStats`` (host scalars, in
        leaf order) and emit its ``repair.step`` event."""
        name = dynamic.TIER_NAMES[int(tier)]
        region_v, region_e = int(region_v), int(region_e)
        reach_rounds, scc_rounds = int(reach_rounds), int(scc_rounds)
        self.repair_tier_steps[name] += 1
        self.repair_region_v_max = max(self.repair_region_v_max, region_v)
        self.repair_region_e_max = max(self.repair_region_e_max, region_e)
        self.repair_reach_rounds += reach_rounds
        self.repair_scc_rounds += scc_rounds
        telemetry.event("repair.step", tier=name, region_v=region_v,
                        region_e=region_e, reach_rounds=reach_rounds,
                        scc_rounds=scc_rounds)

    def _apply_padded(self, ops: dynamic.OpBatch, depth: int = 0
                      ) -> np.ndarray:
        if depth > _MAX_GROW_ROUNDS:
            raise fault_errors.CapacityExhausted(
                "grow-and-replay did not converge; "
                "max_edge_capacity too small for workload?")
        self._compiled.add((int(ops.kind.shape[0]), self._cfg))
        with telemetry.span("service.dispatch", k=1,
                            b=int(ops.kind.shape[0])):
            self._state, ok_dev, ovf_dev, rstats = \
                dynamic.apply_batch_async(self._state, ops, self._cfg)
        # one coalesced host transfer for the step's whole telemetry tuple
        with telemetry.span("service.resolve", steps=1):
            ok_h, ovf, r_h = jax.device_get((ok_dev, ovf_dev, rstats))
        ok = np.array(ok_h)  # own the buffer: replay writes into it below
        self._record_repair(*r_h)
        if int(ovf) == 0:
            return ok
        failed = self._failed_add_lanes(ops, ok)
        if not failed.any():  # overflow already resolved by a later lane
            return ok
        self.grow()
        idx = np.nonzero(failed)[0]
        self.replayed_ops += len(idx)
        for sl, sub in self._sched.chunks(
                np.asarray(ops.kind)[idx], np.asarray(ops.u)[idx],
                np.asarray(ops.v)[idx]):
            n_real = sl.stop - sl.start
            sub_ok = self._apply_padded(sub, depth + 1)[:n_real]
            ok[idx[sl]] = sub_ok
        return ok

    def _failed_add_lanes(self, ops: dynamic.OpBatch, ok: np.ndarray
                          ) -> np.ndarray:
        """AddEdge lanes the table dropped on probe-bound overflow.

        A lane failed iff it is an in-range AddEdge, reported False, both
        endpoints are alive *after* the step (RemoveVertex linearizes
        first, so dead-endpoint lanes were never enabled), and its key is
        absent from the post-step table (present keys mean the False was a
        legitimate duplicate/already-present result).
        """
        kind = np.asarray(ops.kind)
        u = np.asarray(ops.u)
        v = np.asarray(ops.v)
        nv = self._cfg.n_vertices
        in_range = (u >= 0) & (u < nv) & (v >= 0) & (v < nv)
        cand = (kind == dynamic.ADD_EDGE) & in_range & ~ok
        if not cand.any():
            return cand
        alive = np.asarray(self._state.v_alive)
        cand &= alive[np.clip(u, 0, nv - 1)] & alive[np.clip(v, 0, nv - 1)]
        if not cand.any():
            return cand
        found, _ = et.lookup(self._state.edges, ops.u, ops.v,
                             self._cfg.max_probes,
                             impl=self._cfg.sparse_impl)
        return cand & ~np.asarray(found)

    def grow(self, new_capacity: int | None = None):
        """Rehash the edge table into a larger power-of-two capacity and
        re-point ``cfg`` (subsequent steps re-jit under the new config)."""
        cap = new_capacity or self._cfg.edge_capacity * self._grow_factor
        table, cap = self._rehash_preserving(cap)
        self._state = self._state._replace(edges=table)
        self._cfg = dataclasses.replace(self._cfg, edge_capacity=cap)
        self.grow_count += 1

    def _rehash_preserving(self, cap: int):
        """Rehash into ``cap``, doubling further until every live edge
        survives migration.

        ``insert`` can itself exhaust the probe bound at the *target*
        capacity (different keys may collide there that did not collide at
        the source size), and it reports that only through its discarded
        ``placed`` mask -- so we verify by live count and retry bigger.
        """
        live_before, _ = et.fill_stats(self._state.edges)
        for _ in range(_MAX_GROW_ROUNDS):
            if self._max_edge_capacity and cap > self._max_edge_capacity:
                raise fault_errors.CapacityExhausted(
                    f"edge table would exceed max_edge_capacity "
                    f"({cap} > {self._max_edge_capacity})")
            table = _rehash(self._state.edges, cap, self._cfg.max_probes,
                            impl=self._cfg.sparse_impl)
            live_after, _ = et.fill_stats(table)
            if int(live_after) == int(live_before):
                self._live_ub = int(live_after)  # sync already paid
                return table, cap
            cap *= self._grow_factor
        raise fault_errors.CapacityExhausted(
            "table migration kept losing edges; "
            "max_probes too small for workload?")

    def _maybe_compact(self):
        with telemetry.span("service.compact_check"):
            tomb = int(et.fill_stats(self._state.edges)[1])
        if tomb > self._compact_tomb_frac * self._cfg.edge_capacity:
            # rehash at the current capacity == compact, but verified: a
            # compaction that would drop an edge escalates to a grow.
            table, cap = self._rehash_preserving(self._cfg.edge_capacity)
            self._state = self._state._replace(edges=table)
            self._cfg = dataclasses.replace(self._cfg, edge_capacity=cap)
            self.compaction_count += 1

    # ---------------------------------------------------------- queries ---
    # All queries read the last *committed* state: a consistent snapshot
    # whose generation is returned alongside the value (the linearization
    # point of the paper's wait-free readers).

    def _in_range(self, ids) -> np.ndarray:
        return _ids_in_range(ids, self._cfg.n_vertices)

    def same_scc(self, u, v) -> Snapshot:
        """Batched SameSCC(u, v) (paper checkSCC, Alg. 23): absent or
        out-of-range endpoints answer False, never alias a real vertex."""
        st = self._committed
        return Snapshot(same_scc_on(st, self._cfg, u, v), int(st.gen))

    def reachable(self, u, v) -> Snapshot:
        """Batched reachability u[i] ⇝ v[i] on the committed snapshot."""
        st = self._committed
        return Snapshot(reachable_on(st, self._cfg, u, v), int(st.gen))

    def scc_members(self, u) -> Snapshot:
        """bool[NV] membership mask of u's SCC on the committed snapshot."""
        st = self._committed
        if not self._in_range(u).all():
            return Snapshot(np.zeros(self._cfg.n_vertices, bool),
                            int(st.gen))
        res = _members(st, jnp.asarray(u, jnp.int32))
        return Snapshot(np.asarray(res), int(st.gen))

    def community_of(self, u) -> Snapshot:
        """Batched blongsToCommunity (paper §5.3) on the committed
        snapshot; int32 labels, sentinel ``n_vertices`` for absent ids."""
        st = self._committed
        return Snapshot(community_of_on(st, self._cfg, u), int(st.gen))

    def community_sizes(self) -> Snapshot:
        """Community-size histogram on the committed snapshot."""
        st = self._committed
        return Snapshot(community_sizes_on(st, self._cfg), int(st.gen))

    # ------------------------------------------------------------- misc ---

    def edge_set(self) -> set:
        """Host copy of the live edge set (test/debug helper)."""
        t = self._committed.edges
        live = np.asarray(t.state) == int(et.LIVE)
        src = np.asarray(t.src)[live]
        dst = np.asarray(t.dst)[live]
        return set(zip(src.tolist(), dst.tolist()))

    def stats(self) -> dict:
        from repro.kernels.frontier_expand import ops as frontier_ops
        from repro.kernels.hash_probe import ops as hash_probe_ops
        from repro.kernels.reach_blockmm import ops as blockmm_ops
        live, tomb = et.fill_stats(self._committed.edges)
        return {
            # what each kernel hook actually resolves to on this backend
            # at the current capacities ('auto' is size-dependent)
            "kernel_impl": {
                "sparse_impl": self._cfg.sparse_impl,
                "frontier_expand": frontier_ops.resolve_impl(
                    self._cfg.sparse_impl, self._cfg.n_vertices),
                "hash_probe": hash_probe_ops.resolve_impl(
                    self._cfg.sparse_impl, self._cfg.edge_capacity),
                "dense_matmul": blockmm_ops._resolve(
                    self._cfg.dense_matmul_impl),
            },
            "gen": self.gen,
            "n_ccs": int(self._committed.n_ccs),
            "live_edges": int(live),
            "tombstones": int(tomb),
            "edge_capacity": self._cfg.edge_capacity,
            "overflow_total": int(self._committed.overflow),
            "grows": self.grow_count,
            "proactive_grows": self.proactive_grows,
            "replayed_ops": self.replayed_ops,
            "compactions": self.compaction_count,
            "compile_count": self.compile_count,
            "pipelined_chunks": self.pipelined_chunks,
            "fallback_chunks": self.fallback_chunks,
            "scanned_chunks": self.scanned_chunks,
            "scan_dispatches": self.scan_dispatches,
            "repair_dense_steps": self.repair_tier_steps["dense"],
            "repair_compact_steps": self.repair_tier_steps["compact"],
            "repair_full_steps": self.repair_tier_steps["full"],
            "repair_skipped_steps": self.repair_tier_steps["skipped"],
            "repair_region_v_max": self.repair_region_v_max,
            "repair_region_e_max": self.repair_region_e_max,
            "repair_reach_rounds": self.repair_reach_rounds,
            "repair_scc_rounds": self.repair_scc_rounds,
            "deduped_resubmits": self.deduped_resubmits,
        }
