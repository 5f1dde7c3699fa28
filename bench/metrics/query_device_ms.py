"""Device milliseconds per point-query program execution.

The device time of the SameSCC and CommunityOf programs in the trace
over their executions (one per broker flush of one query kind).  Moves
``read_p95_ms``."""

QUERY_PROGRAMS = ("check_scc", "belongs_to_community")


def read(run):
    if run.trace is None:
        return None
    sec, n = run.trace.program(*QUERY_PROGRAMS)
    if n <= 0 or sec <= 0:
        return None
    return 1e3 * sec / n
