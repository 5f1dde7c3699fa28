"""Batched open-addressing hash set over directed edges.

This is the TPU-native replacement for the paper's lock-based lazy list-set:

  paper (lazy list, per-node locks)        here (linear probing, batched)
  ---------------------------------        -------------------------------
  locate(key) pointer walk                 bounded probe loop (vectorized)
  lock(pred); lock(curr); validate         scatter-``min`` claim of a slot:
                                           the lowest op index wins, losers
                                           re-probe -- an obstruction-free
                                           "lock" with deterministic winners
  logical delete (marked = true)           TOMB state (kept for probe chains)
  physical delete / GC                     :func:`compact` rebuild pass

All operations take a *batch* of keys and run in O(max_probes) data-parallel
rounds, entirely inside ``jit``.  The (src, dst, state) columns double as a
COO edge list for the SCC sweeps, so there is no separate adjacency copy.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.hash_probe import ops as hash_probe

EMPTY = jnp.int8(0)
LIVE = jnp.int8(1)
TOMB = jnp.int8(2)
# tables below this many slots grow when a key finds its probe window full
# (:func:`reorder_chain` places it only in larger ones)
RELAYOUT_MIN_CAP = 1 << 20


class EdgeTable(NamedTuple):
    src: jax.Array  # int32[C]
    dst: jax.Array  # int32[C]
    state: jax.Array  # int8[C]  EMPTY | LIVE | TOMB


def empty(capacity: int) -> EdgeTable:
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    return EdgeTable(
        src=jnp.zeros((capacity,), jnp.int32),
        dst=jnp.zeros((capacity,), jnp.int32),
        state=jnp.zeros((capacity,), jnp.int8),
    )


def _hash(u: jax.Array, v: jax.Array, capacity: int) -> jax.Array:
    """Fibonacci-ish mixing of the (u, v) pair into [0, capacity)."""
    u = u.astype(jnp.uint32)
    v = v.astype(jnp.uint32)
    h = u * jnp.uint32(0x9E3779B1) ^ (v + jnp.uint32(0x85EBCA77) + (u << 6) + (u >> 2))
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> 12)
    return (h & jnp.uint32(capacity - 1)).astype(jnp.int32)


def lookup(table: EdgeTable, u: jax.Array, v: jax.Array, max_probes: int,
           *, impl: str = "xla") -> Tuple[jax.Array, jax.Array]:
    """Batched membership probe.

    Returns ``(found: bool[B], slot: int32[B])``; ``slot`` is the LIVE slot
    of the key when found, else the first EMPTY/TOMB slot seen (insertion
    point), else -1 when the probe bound was exhausted.

    ``impl`` picks the probe engine (GraphConfig.sparse_impl semantics):
    the sequential fori_loop below is the ``'xla'`` oracle; the Pallas
    panel sweep (:mod:`repro.kernels.hash_probe`) is bit-identical to it.
    """
    cap = table.src.shape[0]
    base = _hash(u, v, cap)
    b = u.shape[0]
    if hash_probe.resolve_impl(impl, cap) != "xla":
        return hash_probe.probe(table.src, table.dst, table.state, base,
                                u, v, max_probes=max_probes, impl=impl)

    def body(i, carry):
        done, found, slot, free = carry
        pos = (base + i) & (cap - 1)
        st = table.state[pos]
        s, d = table.src[pos], table.dst[pos]
        hit = (st == LIVE) & (s == u) & (d == v)
        is_empty = st == EMPTY
        is_free = st != LIVE
        # remember the first non-live slot as the insertion point
        free = jnp.where((~done) & is_free & (free < 0), pos, free)
        slot = jnp.where((~done) & hit, pos, slot)
        found = found | ((~done) & hit)
        # probing stops at a hit or at a truly EMPTY slot (chain end)
        done = done | hit | is_empty
        return done, found, slot, free

    done = jnp.zeros((b,), jnp.bool_)
    found = jnp.zeros((b,), jnp.bool_)
    slot = jnp.full((b,), -1, jnp.int32)
    free = jnp.full((b,), -1, jnp.int32)
    done, found, slot, free = jax.lax.fori_loop(
        0, max_probes, body, (done, found, slot, free))
    return found, jnp.where(found, slot, free)


def insert(table: EdgeTable, u: jax.Array, v: jax.Array, max_probes: int,
           enable: jax.Array | None = None, *, impl: str = "xla"
           ) -> Tuple[EdgeTable, jax.Array, jax.Array]:
    """Batched insert.  Returns ``(table, inserted: bool[B], failed: bool[B])``.

    ``inserted`` is False for keys already present, duplicate keys within the
    batch (only the first wins -- matching a sequential application order),
    disabled lanes, and probe-bound overflow.  ``failed`` isolates the last
    case: lanes that *wanted* a slot (enabled, key absent, not an intra-batch
    duplicate) but exhausted the probe bound -- the table's own overflow
    report, so callers never need a second probe sweep to detect dropped
    keys.
    """
    cap = table.src.shape[0]
    b = u.shape[0]
    if enable is None:
        enable = jnp.ones((b,), jnp.bool_)

    # intra-batch dedupe: an op is a duplicate if an earlier enabled op has
    # the same key.  B is small (<= few thousand), so O(B log B) sort is fine.
    order = jnp.argsort(v, stable=True)
    order = order[jnp.argsort(u[order], stable=True)]  # lexsort by (u, v)
    su, sv, se = u[order], v[order], enable[order]
    same_prev = jnp.concatenate([
        jnp.zeros((1,), jnp.bool_),
        (su[1:] == su[:-1]) & (sv[1:] == sv[:-1])])
    # within each equal-key run, the first *enabled* op wins; later enabled
    # ops are duplicates (== the sequential order's return values).
    def dup_scan(carry, x):
        same, en = x
        run_carry = jnp.where(same, carry, False)  # reset at run start
        is_dup = run_carry & en
        return run_carry | en, is_dup
    _, dup_sorted = jax.lax.scan(dup_scan, jnp.zeros((), jnp.bool_),
                                 (same_prev, se))
    dup = jnp.zeros((b,), jnp.bool_).at[order].set(dup_sorted)
    enable = enable & ~dup

    # membership probe through the impl hook; the claim loop below stays
    # XLA -- it is an inherently serial linearization (scatter-min winner
    # per round), not a sweep
    found, _ = lookup(table, u, v, max_probes, impl=impl)
    want = enable & ~found

    base = _hash(u, v, cap)

    def round_body(i, carry):
        table, placed, probe = carry
        pending = want & ~placed
        pos = (base + probe) & (cap - 1)
        st = table.state[pos]
        free = st != LIVE
        contend = pending & free
        # scatter-min claim: lowest op index wins the slot this round
        claims = jnp.full((cap,), b, jnp.int32)
        claims = claims.at[jnp.where(contend, pos, cap - 1)].min(
            jnp.where(contend, jnp.arange(b, dtype=jnp.int32), b))
        win = contend & (claims[pos] == jnp.arange(b, dtype=jnp.int32))
        wpos = jnp.where(win, pos, cap)  # out-of-range scatter = drop
        table = EdgeTable(
            src=table.src.at[wpos].set(u, mode="drop"),
            dst=table.dst.at[wpos].set(v, mode="drop"),
            state=table.state.at[wpos].set(LIVE, mode="drop"),
        )
        placed = placed | win
        probe = jnp.where(pending & ~win, probe + 1, probe)
        return table, placed, probe

    placed = jnp.zeros((b,), jnp.bool_)
    probe = jnp.zeros((b,), jnp.int32)
    table, placed, _ = jax.lax.fori_loop(
        0, max_probes, round_body, (table, placed, probe))
    return table, placed, want & ~placed


def reorder_chain(table: EdgeTable, u, v, failed, max_probes: int):
    """Place the first key :func:`insert` reported ``failed`` (all
    ``max_probes`` slots from its home LIVE); returns ``(table, placed)``,
    ``placed`` true on that lane where it went in.

    At the half load the engine keeps, such a run is a clustering
    accident: first-come placement has pushed keys of earlier homes across
    the new key's window.  The key's probe chain -- the slots between the
    EMPTY slot before its home and the first EMPTY slot from it -- is laid
    out again with the new key, in home order: each key at the first slot
    from its home that the keys of smaller homes leave free.  That order
    gives the chain its least displacement; every key still lies within
    its probe bound with no EMPTY slot between its home and it, so lookups
    find it, and the chain's tombstones go.  No key outside the chain
    probes through it.  The lane stays failed where the chain leaves the
    window of ``4 * max_probes`` slots around the home (or the table's
    ends), or a key would land ``max_probes`` or more from its home.  One
    lane a call: two such accidents in one batch are rarer still.  Tables
    below :data:`RELAYOUT_MIN_CAP` slots keep growing instead.
    """
    cap = table.src.shape[0]
    if cap < RELAYOUT_MIN_CAP:
        # a table of at most 9 MB grows cheaply; the tenant engine vmaps
        # such tables, where this lane's sort and window would add ~2 MB of
        # resident program code to every step program
        return table, jnp.zeros_like(failed)
    w = min(4 * max_probes, cap)
    off = jnp.arange(w, dtype=jnp.int32)
    entry = jnp.arange(w + 1, dtype=jnp.int32)
    lane = jnp.argmax(failed)
    h = _hash(u[lane], v[lane], cap)
    start = jnp.clip(h - w // 2, 0, cap - w)
    c = h - start  # the home's offset in the window
    s, d, st = (jax.lax.dynamic_slice(x, (start,), (w,)) for x in table)
    empty = st == EMPTY
    lo = jnp.max(jnp.where(empty & (off < c), off, -1))
    hi = jnp.min(jnp.where(empty & (off >= c), off, w))
    keys = jnp.concatenate([(st == LIVE) & (off > lo) & (off < hi),
                            jnp.ones((1,), jnp.bool_)])
    home = jnp.concatenate([_hash(s, d, cap) - start, c[None]])
    home, ks, kd = jax.lax.sort(
        (jnp.where(keys, home, 2 * w), jnp.concatenate([s, u[lane][None]]),
         jnp.concatenate([d, v[lane][None]])), num_keys=1)
    # home order: each key at max(its home, the previous key's slot + 1)
    pos = entry + jax.lax.cummax(home - entry)
    used = entry < jnp.sum(keys)
    fits = failed[lane] & (lo >= 0) & (hi < w) & jnp.all(
        ~used | ((pos <= hi) & (pos - home < max_probes)))
    to = jnp.where(used & fits, pos, w)
    chain = fits & (off > lo) & (off <= hi)
    window = (s.at[to].set(ks, mode="drop"), d.at[to].set(kd, mode="drop"),
              jnp.where(chain, EMPTY, st).at[to].set(LIVE, mode="drop"))
    table = EdgeTable(*(jax.lax.dynamic_update_slice(x, y, (start,))
                        for x, y in zip(table, window)))
    return table, jnp.zeros_like(failed).at[lane].set(fits)


def remove(table: EdgeTable, u: jax.Array, v: jax.Array, max_probes: int,
           enable: jax.Array | None = None, *, impl: str = "xla"
           ) -> Tuple[EdgeTable, jax.Array]:
    """Batched remove (logical delete -> TOMB).  Returns (table, removed[B])."""
    b = u.shape[0]
    if enable is None:
        enable = jnp.ones((b,), jnp.bool_)
    found, slot = lookup(table, u, v, max_probes, impl=impl)
    hit = found & enable
    # duplicate removals of the same key in one batch target the same slot;
    # both see LIVE pre-state, but sequentially only the first succeeds.
    first = jnp.zeros((b,), jnp.bool_)
    claims = jnp.full((table.src.shape[0],), b, jnp.int32)
    cap = table.src.shape[0]
    claims = claims.at[jnp.where(hit, slot, cap - 1)].min(
        jnp.where(hit, jnp.arange(b, dtype=jnp.int32), b))
    first = hit & (claims[slot] == jnp.arange(b, dtype=jnp.int32))
    wpos = jnp.where(first, slot, cap)
    table = table._replace(state=table.state.at[wpos].set(TOMB, mode="drop"))
    return table, first


def remove_incident(table: EdgeTable, v_mask: jax.Array) -> Tuple[EdgeTable, jax.Array]:
    """Tombstone every LIVE edge with an endpoint in ``v_mask`` (bool[NV]).

    This is the paper's "trim the SCC-Graph after RemoveVertex" -- with a
    dense table it is one masked compare over the columns instead of a walk.
    Returns (table, was_removed mask over slots).
    """
    live = table.state == LIVE
    kill = live & (v_mask[table.src] | v_mask[table.dst])
    return table._replace(
        state=jnp.where(kill, TOMB, table.state)), kill


def rehash(table: EdgeTable, new_capacity: int, max_probes: int,
           *, impl: str = "xla") -> EdgeTable:
    """Migrate every LIVE entry into a fresh table of ``new_capacity``.

    The grow half of grow-and-replay: the host detects probe-bound overflow
    (``GraphState.overflow`` delta), picks a geometrically larger capacity,
    and calls this inside jit (``new_capacity`` is static, so each target
    capacity compiles once).  Tombstones are dropped for free, so
    ``rehash(t, cap(t))`` == :func:`compact`.
    """
    assert new_capacity & (new_capacity - 1) == 0, (
        "new_capacity must be a power of two")
    live = table.state == LIVE
    fresh = empty(new_capacity)
    fresh, _, _ = insert(fresh, table.src, table.dst, max_probes,
                         enable=live, impl=impl)
    return fresh


def compact(table: EdgeTable, max_probes: int, *, impl: str = "xla"
            ) -> EdgeTable:
    """GC pass: rebuild the table without tombstones (hazard-pointer
    analogue) -- rehash at the current capacity."""
    return rehash(table, table.src.shape[0], max_probes, impl=impl)


def fill_stats(table: EdgeTable):
    live = jnp.sum(table.state == LIVE)
    tomb = jnp.sum(table.state == TOMB)
    return live, tomb
