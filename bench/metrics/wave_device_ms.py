"""Device milliseconds per admission wave of the tenant engine.

The device time of the vmapped fused-scan programs in the trace, over
the waves the admission queue flushed in the window.  Moves
``update_ops_s``."""

WAVE_PROGRAMS = ("_vmapped_scan",)


def read(run):
    if run.trace is None:
        return None
    sec, _ = run.trace.program(*WAVE_PROGRAMS)
    waves = run.delta("waves")
    if waves <= 0 or sec <= 0:
        return None
    return 1e3 * sec / waves
