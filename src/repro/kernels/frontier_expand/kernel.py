"""Frontier expansion as a one-hot panel sweep (Pallas TPU kernel).

One round of sparse frontier propagation is a *segment-min*: every live
edge (src -> dst) carries a message (0/SENTINEL for boolean reachability,
a hashed priority or min-label otherwise) and each vertex takes the
minimum over its incoming messages.  XLA lowers that to a serialized
scatter-min; the TPU-native formulation is the same one-hot trade as
``kernels/embedding_bag``: sweep the vertex space in ``bv``-wide panels,
build the panel x edge-block membership mask ``eq[v, e] = (dst[e] == v)``
on the VPU, and min-reduce the masked messages into a resident output
tile.  Gathers become dense compares -- the right trade exactly when
scatter bandwidth, not compute, is the roofline term (compact repair
regions, batched query frontiers).

The kernel reduces **int32**: the TPU has no unsigned-integer reductions,
so the wrapper (ops.py) maps the uint32 messages through the
order-preserving embedding ``x ^ 0x80000000`` (bitcast to int32) and back.
The uint32 identity 0xFFFFFFFF lands on INT32_MAX, this kernel's
SENTINEL.

Grid is ``(F/bf, NV/bv, E/be)`` with the edge axis innermost, so each
(frontier, vertex-panel) output tile stays resident across the whole edge
sweep; it is initialized to SENTINEL at edge-block 0 (the min-semiring
identity), mirroring the ``@pl.when(j == 0)`` accumulator idiom of the
other kernels.  Per grid step VMEM: dst (be*4B) + msg (bf*be*4B) + the
(bf, bv, be) masked broadcast + out (bf*bv*4B) -- defaults bf<=8, bv=128,
be=256 keep it under ~1.2 MiB << 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SENTINEL = 0x7FFFFFFF  # int32 identity of the min-semiring


def _kernel(dst_ref, msg_ref, o_ref, *, bv: int):
    i = pl.program_id(1)  # vertex panel
    k = pl.program_id(2)  # edge block

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, SENTINEL)

    d = dst_ref[...]                                       # (1, be) int32
    m = msg_ref[...]                                       # (bf, be) int32
    vids = i * bv + jax.lax.broadcasted_iota(
        jnp.int32, (bv, d.shape[1]), 0)                    # (bv, be)
    eq = d == vids                                         # (bv, be)
    contrib = jnp.where(eq[None, :, :], m[:, None, :],
                        jnp.int32(SENTINEL))               # (bf, bv, be)
    o_ref[...] = jnp.minimum(o_ref[...], jnp.min(contrib, axis=2))


@functools.partial(jax.jit,
                   static_argnames=("nvp", "bf", "bv", "be", "interpret"))
def segment_min_i32(dst, msg, *, nvp: int, bf: int, bv: int, be: int,
                    interpret: bool = True):
    """dst: int32[1, Ep] (pad = -1), msg: int32[Fp, Ep] -> int32[Fp, NVp].

    Fp % bf == 0, Ep % be == 0, NVp % bv == 0 (ops.py pads).
    """
    fp, ep = msg.shape
    assert fp % bf == 0 and ep % be == 0 and nvp % bv == 0, \
        (fp, ep, nvp, bf, be, bv)
    return pl.pallas_call(
        functools.partial(_kernel, bv=bv),
        grid=(fp // bf, nvp // bv, ep // be),
        in_specs=[
            pl.BlockSpec((1, be), lambda f, i, k: (0, k)),
            pl.BlockSpec((bf, be), lambda f, i, k: (f, k)),
        ],
        out_specs=pl.BlockSpec((bf, bv), lambda f, i, k: (f, i)),
        out_shape=jax.ShapeDtypeStruct((fp, nvp), jnp.int32),
        interpret=interpret,
    )(dst, msg)
