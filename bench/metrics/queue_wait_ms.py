"""Milliseconds an admitted update waits in the admission queue.

The mean of the program's ``queue.wait`` records (``tenancy/queue.py``),
one per admitted ticket, from its enqueue to the start of the wave that
carries it.  Moves ``update_p95_ms``."""

from bench import spans


def read(run):
    recs = spans.window(run, "queue.wait")
    return None if recs is None else spans.mean_ms(recs)
