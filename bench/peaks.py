"""Peak rates of each accelerator, and the least bytes each kernel needs.

Keyed by ``device_kind`` as JAX reports it.  A device that is not in the
table is an error, never a default.

Source of the v5e row: Google Cloud documentation, "TPU v5e" (per chip:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
"""
from __future__ import annotations

import math

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"add a row with its source to bench/peaks.py"
                       ) from None


def _numel(shape) -> int:
    return math.prod(shape)


def frontier_min_bytes(dst_shape, msg_shape, out_shape) -> int:
    """Segment-min of per-edge messages into vertices: read every edge's
    destination once (int32), every message once (F x E int32), write
    every output once (F x NV int32).  Leading axes, as a vmap adds, are
    part of the shapes.  Any formulation must move at least this."""
    return 4 * (_numel(dst_shape) + _numel(msg_shape) + _numel(out_shape))


def probe_bytes(batch_shape) -> int:
    """Batched membership probe of B keys: read u, v and the hashed base
    (3 x int32), write found and slot (1 + 4 bytes), and read at least
    one table slot (src, dst, state: 9 bytes) per key."""
    b = _numel(batch_shape)
    return b * (4 + 4 + 4) + b * (1 + 4) + b * 9
