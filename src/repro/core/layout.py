"""The live edges sorted by each endpoint, and the repair sweeps that run
on them.

A TPU executes a random scatter element by element, so above the sweep
kernel's vertex ceiling (``frontier_expand.ops.AUTO_MAX_NV``) a
full-table round of :mod:`repro.core.reach` -- gathers and a scatter-min
over every slot of the edge table -- costs ~10 ns a slot.  Here the live
edges are sorted once per repairing step, by destination and by source,
into an :class:`EdgeLayout` of ``H = C/2`` entries per order (the engine
keeps its ``C``-slot table at or under half load), and every round of the
FW/BW, trim and propagation fixpoints gathers the vertex values at the
far endpoints and reduces each vertex's sorted segment with a segmented
scan: a gather over half the table and no scatter.  Integer min does not
depend on order, so outputs and round counts equal the scatter sweeps'
bit for bit (tests/test_sorted_sweeps.py).  No edge changes while a
repair runs, so one layout serves every round of a step; a table with
more than ``H`` live slots cannot be laid out, and its caller runs the
scatter sweeps instead (:func:`fits`).

A TPU keeps every loaded program's code in device memory, and each call
site of a sort or a scan adds to it, so the code sites are few: one
:func:`build`; the two sweeps of a pair (forward and backward reach, the
forward and backward propagations) run as one loop over the order
(:func:`_fixpoint_pair`); trim and the fused FW/BW reduce the two orders
in one loop (:func:`pull_both`).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import reach
from repro.kernels.frontier_expand import ops as frontier

SENT = reach.SENT
INT32_MAX = jnp.iinfo(jnp.int32).max
INT32_MIN = jnp.iinfo(jnp.int32).min

# what a repairing step's sweeps ran on (RepairStats.layout)
LAYOUT_NONE, LAYOUT_SORTED, LAYOUT_FALLBACK = 0, 1, 2
LAYOUT_NAMES = ("none", "sorted", "fallback")


def uses_layout(impl: str, nv: int) -> bool:
    """Whether the repair sweeps over ``nv`` vertices run on an
    :class:`EdgeLayout`: ``'sorted'`` forces it, and ``'auto'`` takes it on
    a TPU above the sweep kernel's vertex ceiling."""
    if impl == "sorted":
        return True
    return (impl == "auto" and jax.default_backend() == "tpu"
            and nv > frontier.AUTO_MAX_NV)


class EdgeLayout(NamedTuple):
    """The live edges in two orders: order 0 groups them by destination (a
    vertex pulls from its predecessors), order 1 by source.  The edges of
    head ``v`` in order ``k`` are entries ``[ends[k][v], ends[k][v + 1])``
    of ``tails[k]``, which holds each entry's other endpoint, with the
    sign bit set on the entry that opens its head's segment: the segmented
    scans need that bit, and a mask of its own would cost memory.  Entries
    past ``ends[k][-1]`` are padding that no segment reads."""
    tails: tuple   # (int32[H], int32[H])
    ends: tuple    # (int32[NV + 1], int32[NV + 1])

    def order(self, k):
        """``(tail, ends)`` of order ``k``, which may be traced: a select
        that the round's first pass reads through, where a slice of
        stacked orders would cost a copy and its code."""
        if isinstance(k, int):
            return self.tails[k], self.ends[k]
        return jnp.where(k == 0, *self.tails), jnp.where(k == 0, *self.ends)


def capacity(n_slots: int) -> int:
    """Entries per order in the layout of an ``n_slots``-slot table."""
    return max(n_slots // 2, 1)


def fits(live) -> jax.Array:
    """bool[]: whether the live slots fit a layout."""
    return jnp.sum(live) <= capacity(live.shape[0])


def _scan(combine, elems, fill):
    """Inclusive scan along the last axis of a tuple of arrays by
    ``combine(left, right)``, Hillis-Steele: log2(n) elementwise passes,
    each combining every entry with the one ``2^k`` before it (``fill``
    where there is none), run as one loop so that its code exists once."""
    n = elems[0].shape[-1]

    def level(i, elems):
        k = jnp.left_shift(jnp.int32(1), i)
        left = tuple(jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([jnp.full(x.shape, f, x.dtype), x], axis=-1),
            n - k, n, axis=-1) for x, f in zip(elems, fill))
        return combine(left, elems)

    return jax.lax.fori_loop(0, max(n - 1, 0).bit_length(), level, elems)


def _segments(head, tail, nv: int):
    """One order's ``(tail, ends)`` from its entries sorted by ``head``
    (``nv`` heads the padding)."""
    prev = jnp.concatenate([jnp.full((1,), -1, head.dtype), head[:-1]])
    # each head's entry count, from a sorted scatter-add, summed
    count = jax.ops.segment_sum(jnp.ones(head.shape, jnp.int32), head,
                                nv + 1, indices_are_sorted=True)
    ends, = _scan(lambda a, b: (a[0] + b[0],),
                  (jnp.concatenate([jnp.zeros((1,), jnp.int32), count]),),
                  (0,))
    return jnp.where(head != prev, tail | INT32_MIN, tail), ends[:nv + 1]


def build(src, dst, live, nv: int) -> EdgeLayout:
    """Sort the live slots of a ``C``-slot table into both orders, ``H =
    C/2`` entries each; valid only where :func:`fits`.  Only the first
    sort spans the whole table: the source order is sorted from the
    destination order's ``H`` entries."""
    h = capacity(live.shape[0])
    # dead slots sort last under the key nv, past every segment
    head, tail = jax.lax.sort((jnp.where(live, dst, nv), src), num_keys=1,
                              is_stable=False)
    head, tail = head[:h], tail[:h]
    into = _segments(head, tail, nv)
    # padding keeps key nv, and its tail a valid vertex id
    head, tail = jax.lax.sort((jnp.where(head < nv, tail, nv),
                               jnp.minimum(head, nv - 1)), num_keys=1,
                              is_stable=False)
    out_of = _segments(head, tail, nv)
    return EdgeLayout(*zip(into, out_of))


def _reduce_min(vals, far, opens, lo, hi):
    """uint32[V]: the minimum of ``vals`` at the ``far`` endpoints of each
    head's entries ``[lo, hi)``, SENT for a head with none: a scan that
    restarts where ``opens``, read at each segment's last entry."""
    def combine(a, b):
        return a[0] | b[0], jnp.where(b[0], b[1], jnp.minimum(a[1], b[1]))

    _, run = _scan(combine, (opens, vals[far]), (True, SENT))
    return jnp.where(hi > lo, run[jnp.maximum(hi - 1, 0)], SENT)


def pull(vals, layout: EdgeLayout, order):
    """uint32[NV]: the minimum of ``vals`` over each vertex's predecessors
    (``order`` 0) or successors (1), SENT where it has none.  ``order``
    may be traced."""
    tail, ends = layout.order(order)
    return _reduce_min(vals, tail & INT32_MAX, tail < 0, ends[:-1],
                       ends[1:])


def pull_both(vals, layout: EdgeLayout):
    """uint32[2, NV]: :func:`pull` of ``vals[k]`` in order ``k``, both
    orders in one loop."""
    def one(order, out):
        return out.at[order].set(pull(vals[order], layout, order))

    return jax.lax.fori_loop(0, 2, one, vals)


def count_within(mask, layout: EdgeLayout):
    """int32[]: the live edges with both endpoints in ``mask``: each
    vertex's segment of order 0 counts its predecessors in ``mask``, by a
    prefix sum read at the segment ends."""
    tail, ends = layout.order(0)
    run, = _scan(lambda a, b: (a[0] + b[0],),
                 (mask[tail & INT32_MAX].astype(jnp.int32),), (0,))
    run = jnp.concatenate([jnp.zeros((1,), jnp.int32), run])
    return jnp.sum(jnp.where(mask, run[ends[1:]] - run[ends[:-1]], 0))


def _fixpoint_pair(body, init0, init1, max_iters: int):
    """:func:`repro.core.reach._fixpoint` of ``body(0, .)`` from ``init0``,
    then of ``body(1, .)`` from ``init1``, as one loop, so that the body's
    code exists once.  Returns ``(x0, x1, rounds)``, rounds summed over
    both."""
    def cond(carry):
        return carry[4] < 2

    def step(carry):
        x, other, changed, it, order, rounds = carry

        def run(_):
            nxt, changed = body(order, x)
            return nxt, other, changed, it + 1, order, rounds + 1

        def switch(_):
            return other, x, jnp.bool_(True), jnp.int32(0), order + 1, rounds

        return jax.lax.cond(changed & (it < max_iters), run, switch, None)

    x0, x1, _, _, _, rounds = jax.lax.while_loop(
        cond, step, (init0, init1, jnp.bool_(True), jnp.int32(0),
                     jnp.int32(0), jnp.int32(0)))
    return x0, x1, rounds


def _reached(vals):
    return jnp.where(vals, reach.ZERO_U32, SENT)


def reach_pair(layout: EdgeLayout, seed_f, seed_b, allowed, max_iters: int,
               spec=None):
    """:func:`repro.core.reach.forward_reach` from ``seed_f`` and
    :func:`~repro.core.reach.backward_reach` from ``seed_b`` on the
    layout: ``(fw, bw, rounds)``, rounds the sum of the two sweeps'."""
    def body(order, reached):
        hit = pull(_reached(reached), layout, order) == 0
        nxt = reach._constrain(reached | (hit & allowed), spec)
        return nxt, jnp.any(nxt != reached)

    return _fixpoint_pair(body, reach._constrain(seed_f & allowed, spec),
                          reach._constrain(seed_b & allowed, spec),
                          max_iters)


def fused_reach(layout: EdgeLayout, seed_f, seed_b, allowed,
                max_iters: int, spec=None):
    """:func:`repro.core.reach.fused_fw_bw_reach` on the layout."""
    pin = (None if spec is None
           else jax.sharding.PartitionSpec(None, *spec))

    def body(reached):
        hit = pull_both(_reached(reached), layout) == 0
        nxt = reach._constrain(reached | (hit & allowed[None, :]), pin)
        return nxt, jnp.any(nxt != reached)

    reached, rounds = reach._fixpoint(
        body, reach._constrain(jnp.stack([seed_f & allowed,
                                          seed_b & allowed]), pin),
        max_iters)
    return reached[0], reached[1], rounds


def propagate_pair(layout: EdgeLayout, labels, allowed, max_iters: int,
                   spec=None, shortcut: bool = False):
    """:func:`repro.core.reach.propagate_min_labels` of ``labels`` along
    the edges and along the reversed edges, on the layout: ``(fwd, bwd,
    rounds)``."""
    nv = labels.shape[0]
    sentinel = jnp.iinfo(labels.dtype).max

    def body(order, lab):
        incoming = pull(jnp.where(allowed, lab.astype(jnp.uint32), SENT),
                        layout, order)
        incoming = jnp.minimum(incoming, jnp.uint32(sentinel)).astype(
            lab.dtype)
        nxt = jnp.where(allowed, jnp.minimum(lab, incoming), lab)
        if shortcut:
            hop = nxt[jnp.clip(nxt, 0, nv - 1)]
            nxt = jnp.where(allowed & (nxt < sentinel),
                            jnp.minimum(nxt, hop), nxt)
        nxt = reach._constrain(nxt, spec)
        return nxt, jnp.any(nxt != lab)

    return _fixpoint_pair(body, labels, labels, max_iters)


def prio_pair(layout: EdgeLayout, active, max_iters: int, spec=None):
    """:func:`repro.core.reach.propagate_min_prio` along the edges and
    along the reversed edges, on the layout: ``(fwd, bwd, rounds)``."""
    nv = active.shape[0]
    assert nv < reach.SENT_PREIMAGE
    lab0 = jnp.where(active, reach._prio(jnp.arange(nv, dtype=jnp.int32)),
                     reach.PRIO_SENT)

    def body(order, lab):
        incoming = pull(jnp.where(active, lab, reach.PRIO_SENT), layout,
                        order)
        nxt = jnp.where(active, jnp.minimum(lab, incoming), lab)
        # pointer jump through the witness vertex
        hop = nxt[jnp.clip(reach._unprio(nxt), 0, nv - 1)]
        nxt = jnp.where(active & (nxt != reach.PRIO_SENT),
                        jnp.minimum(nxt, hop), nxt)
        nxt = reach._constrain(nxt, spec)
        return nxt, jnp.any(nxt != lab)

    fwd, bwd, rounds = _fixpoint_pair(body, lab0, lab0, max_iters)

    def witness(lab):
        return jnp.where(lab != reach.PRIO_SENT, reach._unprio(lab), nv)

    return witness(fwd), witness(bwd), rounds
