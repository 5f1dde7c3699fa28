"""Open-addressing probe walk as a data-parallel panel sweep (Pallas TPU).

The sequential probe loop (``edge_table.lookup``) is O(max_probes) serial
rounds of gather -> compare -> select per batch; each round is a
random-index gather, the classic scatter/gather roofline.  The fused
formulation: sweep the table in ``bc``-wide panels and reduce, per query
lane, three *offset minima* over the lane's probe window
``off(slot) = (slot - hash(u, v)) & (C - 1)``:

  min_hit    first in-window LIVE slot matching the key,
  min_empty  first in-window EMPTY slot (where the sequential walk stops),
  min_free   first in-window non-LIVE slot (the insertion point).

Because a probe window is a *contiguous* run of offsets, the sequential
walk's outcome is a pure function of those minima (ops.py reconstructs
``(found, slot)`` bit-identically): the walk hits iff the first match
precedes the first EMPTY, and the insertion point is the first non-LIVE
offset.  TOMB chains and wrap-around fall out of the modular offset.

Grid ``(B/bb, C/bc)`` with the table axis innermost, so each lane tile's
three minima stay resident across the sweep (init to the SENTINEL
``max_probes`` at panel 0).  All arrays are (1, N) lane-major rows, so
the batch tiles are lane-dense: ``bb`` is a multiple of 128, or the whole
batch when it is smaller (the TPU block rule for a minor dimension).  The
compare broadcast is (1, bb, bc) -- bb=128, bc=512 is 256 KiB per live
int32 intermediate, about 2 MiB of VMEM in all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EMPTY, LIVE, TOMB = 0, 1, 2


def _kernel(u_ref, v_ref, base_ref, src_ref, dst_ref, st_ref,
            hit_ref, empty_ref, free_ref, *, cap: int, max_probes: int,
            bc: int):
    j = pl.program_id(1)  # table panel

    @pl.when(j == 0)
    def _init():
        hit_ref[...] = jnp.full_like(hit_ref, max_probes)
        empty_ref[...] = jnp.full_like(empty_ref, max_probes)
        free_ref[...] = jnp.full_like(free_ref, max_probes)

    u3 = u_ref[...][:, :, None]                            # (1, bb, 1)
    v3 = v_ref[...][:, :, None]
    base3 = base_ref[...][:, :, None]
    slots = j * bc + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, bc), 2)                          # (1, 1, bc)
    # first-visit offset of each slot in this lane's probe sequence; the
    # power-of-two mask makes negatives wrap exactly like the walk does
    off = (slots - base3) & (cap - 1)                      # (1, bb, bc)
    inw = off < max_probes
    s3 = src_ref[...][:, None, :]                          # (1, 1, bc)
    d3 = dst_ref[...][:, None, :]
    st3 = st_ref[...][:, None, :]
    sent = jnp.int32(max_probes)
    hit = inw & (st3 == LIVE) & (s3 == u3) & (d3 == v3)
    is_empty = inw & (st3 == EMPTY)
    is_free = inw & (st3 != LIVE)
    hit_ref[...] = jnp.minimum(
        hit_ref[...], jnp.min(jnp.where(hit, off, sent), axis=2))
    empty_ref[...] = jnp.minimum(
        empty_ref[...], jnp.min(jnp.where(is_empty, off, sent), axis=2))
    free_ref[...] = jnp.minimum(
        free_ref[...], jnp.min(jnp.where(is_free, off, sent), axis=2))


@functools.partial(jax.jit,
                   static_argnames=("max_probes", "bb", "bc", "interpret"))
def probe_sweep(u, v, base, src, dst, state, *, max_probes: int, bb: int,
                bc: int, interpret: bool = True):
    """u/v/base: int32[1, Bp]; src/dst/state: int32[1, C] table rows.

    Bp % bb == 0 and C % bc == 0 (ops.py pads/chooses).  Returns three
    int32[1, Bp] offset minima (SENTINEL = max_probes).
    """
    bp = u.shape[1]
    cap = src.shape[1]
    assert bp % bb == 0 and cap % bc == 0, (bp, cap, bb, bc)
    spec_b = pl.BlockSpec((1, bb), lambda i, j: (0, i))
    spec_t = pl.BlockSpec((1, bc), lambda i, j: (0, j))
    out = jax.ShapeDtypeStruct((1, bp), jnp.int32)
    return pl.pallas_call(
        functools.partial(_kernel, cap=cap, max_probes=max_probes, bc=bc),
        grid=(bp // bb, cap // bc),
        in_specs=[spec_b, spec_b, spec_b, spec_t, spec_t, spec_t],
        out_specs=[spec_b, spec_b, spec_b],
        out_shape=[out, out, out],
        interpret=interpret,
    )(u, v, base, src, dst, state)
