"""Share of the HBM roofline reached by the ``frontier_expand`` kernel.

For every call of the kernel in the trace, the least bytes the
segment-min needs (``bench.peaks.frontier_min_bytes`` of the call's
operand shapes) over the chip's HBM bandwidth is the least time the call
could take; the share is the sum of those over the kernel's device time.
Moves ``update_ops_s``."""

from bench import peaks
from bench.trace import operand_shapes


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.kernel_calls.get("frontier_min", [])
    sec = sum(s for s, _ in calls)
    if not calls or sec <= 0:
        return None
    need = 0
    for _, text in calls:
        out, dst, msg = operand_shapes(text)[:3]
        need += peaks.frontier_min_bytes(dst, msg, out)
    bw = peaks.peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / sec
