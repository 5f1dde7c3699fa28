"""Device milliseconds per fixpoint round of the update step.

The device time of the update-step programs in the trace (the programs
``step_device_ms`` reads) over the rounds of the window's
``repair.step`` events.  Times ``repair_rounds_per_step`` it gives
``step_device_ms``: it splits a step into how many rounds and how long
each takes.  Moves ``update_p95_ms``."""

from bench import spans

STEP_PROGRAMS = ("_apply_batch_impl", "_apply_batch_scan_impl")


def read(run):
    if run.trace is None:
        return None
    recs = spans.window(run, "repair.step")
    if recs is None:
        return None
    sec, _ = run.trace.program(*STEP_PROGRAMS)
    n = spans.rounds(recs)
    if n <= 0 or sec <= 0:
        return None
    return 1e3 * sec / n
