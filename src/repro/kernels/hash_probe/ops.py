"""Public hash-probe wrapper: resolve impl, pad, reconstruct (found, slot).

Same 'auto' asymmetry as frontier_expand: lookups back every table op on
the always-on update path, so CPU 'auto' is the XLA probe loop and the
Pallas paths are covered by the forced-'pallas_interpret' differential
suites.  On TPU, 'auto' additionally falls back to 'xla' above
AUTO_MAX_CAP -- the panel sweep reads the whole table per batch
(O(B + C) panels), which beats the serial O(max_probes) gather walk only
while the table fits a few VMEM-sized sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.hash_probe import kernel, ref

AUTO_MAX_CAP = 1 << 16


def resolve_impl(impl: str, cap: int | None = None) -> str:
    if impl != "auto":
        return impl
    if jax.default_backend() == "tpu" and (cap is None
                                           or cap <= AUTO_MAX_CAP):
        return "pallas"
    return "xla"


def probe(src, dst, state, base, u, v, *, max_probes: int,
          impl: str = "auto", bb: int = 128, bc: int = 512):
    """Batched open-addressing membership probe.

    src/dst: int32[C], state: int{8,32}[C] (0=EMPTY/1=LIVE/2=TOMB), base:
    int32[B] hashed start slots, u/v: int32[B] keys; C a power of two.
    Returns ``(found: bool[B], slot: int32[B])`` with
    :func:`repro.core.edge_table.lookup` semantics, bit-identical across
    impls.
    """
    with jax.named_scope("probe"):
        cap = src.shape[0]
        impl = resolve_impl(impl, cap)
        if impl == "xla":
            return ref.probe(src, dst, state, base, u, v,
                             max_probes=max_probes)
        b = u.shape[0]
        bc = min(bc, cap)
        # lane-dense batch tiles: one whole-batch block up to bb, else bb-wide
        # blocks (bb % 128 == 0) over the batch padded to a multiple of bb
        assert bb % 128 == 0, bb
        bp = b if b <= bb else -(-b // bb) * bb
        bb_eff = min(bb, max(bp, 1))

        def row(x, pad_to, fill):
            x = x.astype(jnp.int32).reshape(1, -1)
            return jnp.pad(x, ((0, 0), (0, pad_to - x.shape[1])),
                           constant_values=fill)

        hit_off, empty_off, free_off = kernel.probe_sweep(
            row(u, bp, -1), row(v, bp, -1), row(base, bp, 0),
            row(src, cap, 0), row(dst, cap, 0), row(state, cap, 0),
            max_probes=max_probes, bb=bb_eff, bc=bc,
            interpret=(impl == "pallas_interpret"))
        hit_off = hit_off[0, :b]
        empty_off = empty_off[0, :b]
        free_off = free_off[0, :b]
        # the sequential walk stops at min(hit, empty): it found the key iff
        # the first match precedes the first EMPTY; otherwise it reports the
        # first non-LIVE slot it saw (or -1 when the window held none)
        found = hit_off < empty_off
        mask = cap - 1
        pos_hit = (base + hit_off) & mask
        pos_free = jnp.where(free_off < max_probes, (base + free_off) & mask,
                             -1)
        return found, jnp.where(found, pos_hit, pos_free)
