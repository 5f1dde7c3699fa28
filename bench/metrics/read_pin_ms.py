"""Milliseconds a broker flush takes to pin its snapshot.

The mean of the program's ``broker.pin`` spans (``core/broker.py``), one
per flush: fetching the committed state, config and generation, which
for a tenant takes the service's and the engine's locks and slices the
tenant's lane.  Moves ``read_p95_ms``."""

from bench import spans


def read(run):
    recs = spans.window(run, "broker.pin")
    return None if recs is None else spans.mean_ms(recs)
