"""Share of the HBM roofline reached by the ``hash_probe`` kernel.

For every call of the kernel in the trace, the least bytes a batched
probe of its B keys needs (``bench.peaks.probe_bytes``) over the chip's
HBM bandwidth is the least time the call could take; the share is the
sum of those over the kernel's device time.  Moves ``update_ops_s``."""

from bench import peaks
from bench.trace import operand_shapes


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.kernel_calls.get("probe", [])
    sec = sum(s for s, _ in calls)
    if not calls or sec <= 0:
        return None
    need = sum(peaks.probe_bytes(operand_shapes(text)[0])
               for _, text in calls)
    bw = peaks.peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / sec
