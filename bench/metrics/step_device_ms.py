"""Device milliseconds per update step of the single-graph service.

The device time of the jitted update-step programs in the trace, over
the steps the service's repair counters saw in the window (each step
reports exactly one repair tier).  Moves ``update_ops_s``."""

STEP_PROGRAMS = ("_apply_batch_impl", "_apply_batch_scan_impl")


def read(run):
    if run.trace is None:
        return None
    sec, _ = run.trace.program(*STEP_PROGRAMS)
    steps = sum(run.delta("steps").values())
    if steps <= 0 or sec <= 0:
        return None
    return 1e3 * sec / steps
