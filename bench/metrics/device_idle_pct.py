"""Share of the traced window in which no operation ran on the device.

Busy time is the union of the device's operation intervals in the
profiler trace, averaged over the chips; idle is the rest of the window.
Moves ``update_ops_s``: the host path keeps the chip idle."""


def read(run):
    t = run.trace
    if t is None or t.busy_s is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
