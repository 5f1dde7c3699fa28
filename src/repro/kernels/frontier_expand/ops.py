"""Public frontier-expansion wrapper: resolve impl, pad, dispatch.

``impl='auto'`` is deliberately asymmetric to ``dense_matmul_impl``: the
sparse sweep is the *always-on* hot loop (every fixpoint round of every
repair), not an opt-in tier, so 'auto' resolves to the XLA scatter on CPU
instead of interpret mode -- interpret-executing an O(E x NV) panel sweep
per round would regress the whole service by orders of magnitude.  The
Pallas paths stay covered on CPU by the differential suites
(tests/test_sparse_kernels.py, test_scan_engine.py), which force
'pallas_interpret' explicitly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.frontier_expand import kernel, ref

SENTINEL = jnp.uint32(ref.SENTINEL)
# order-preserving uint32 <-> int32 embedding: flipping the top bit maps
# [0, 2^32) monotonically onto [INT32_MIN, INT32_MAX], so ref.SENTINEL
# lands on kernel.SENTINEL and a signed min is the unsigned min
_FLIP = np.uint32(0x80000000)


def _to_i32(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.uint32) ^ _FLIP,
                                        jnp.int32)


def _from_i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32) ^ _FLIP


# 'auto' stops densifying above this vertex count even on TPU: the panel
# kernel visits O(E * NV / (bv * be)) tiles per round while the XLA
# scatter stays O(E); past ~2^18 vertices the one-hot trade loses.  The
# compact repair tier sweeps region_vertex_capacity = n_vertices / 8
# slots (2^17 at 2^20 vertices), so it resolves to the kernel here.
AUTO_MAX_NV = 1 << 18


def resolve_impl(impl: str, nv: int | None = None) -> str:
    if impl != "auto":
        return impl
    if jax.default_backend() == "tpu" and (nv is None or nv <= AUTO_MAX_NV):
        return "pallas"
    return "xla"


def frontier_min(dst, msg, nv: int, *, impl: str = "auto",
                 bf: int = 8, bv: int = 128, be: int = 256):
    """Segment-min of per-edge messages into their destination vertices.

    dst: int32[E]; msg: uint32[E] or uint32[F, E].  Returns uint32[NV] /
    uint32[F, NV]: out[v] = min(msg[e] : dst[e] == v), SENTINEL where no
    edge lands.  One frontier-expansion round in the min-semiring (bool
    reachability maps reached -> 0, blocked -> SENTINEL); bit-identical
    across impls.
    """
    with jax.named_scope("frontier_min"):
        impl = resolve_impl(impl, nv)
        squeeze = msg.ndim == 1
        m2 = msg[None, :] if squeeze else msg
        if impl == "xla":
            out = ref.frontier_min(dst, m2, nv)
            return out[0] if squeeze else out
        f, e = m2.shape
        fp = f if f <= bf else -(-f // bf) * bf
        bf_eff = min(bf, max(fp, 1))
        ep = max(be, -(-e // be) * be)
        nvp = -(-nv // bv) * bv
        # pad lanes can never land: dst -1 matches no panel vertex id, and the
        # padded messages are the min identity anyway
        dst_p = jnp.pad(dst.reshape(1, -1).astype(jnp.int32),
                        ((0, 0), (0, ep - e)), constant_values=-1)
        msg_p = jnp.pad(_to_i32(m2), ((0, fp - f), (0, ep - e)),
                        constant_values=kernel.SENTINEL)
        out = kernel.segment_min_i32(
            dst_p, msg_p, nvp=nvp, bf=bf_eff, bv=bv, be=be,
            interpret=(impl == "pallas_interpret"))[:f, :nv]
        out = _from_i32(out)
        return out[0] if squeeze else out
