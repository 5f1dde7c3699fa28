"""Fixpoint iterations per update step of the single-graph service.

From the program's ``repair.step`` events, one per resolved step: the
mean of ``reach_rounds`` (the FW/BW sweep before the tier choice) plus
``scc_rounds`` (every trim and propagation iteration of the chosen
tier).  A step the repair gate skipped counts 0.  Moves
``update_p95_ms``: each round sweeps the region, or the whole table on
the full tier."""

from bench import spans


def read(run):
    recs = spans.window(run, "repair.step")
    return None if recs is None else spans.rounds(recs) / len(recs)
