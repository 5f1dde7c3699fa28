"""Share of the tenant engine's lane-steps repaired by the dense tier.

From the program's ``engine.wave`` events (``tenancy/engine.py``): the
dense-tier steps over all real lane-steps of the window's waves (never
their padding rows).  Where a lane's every repair is one dense closure,
this equals ``lane_repair_pct``.  Moves ``update_ops_s``."""

from bench import spans


def read(run):
    recs = spans.window(run, "engine.wave")
    if recs is None:
        return None
    steps = sum(r.attrs["lane_steps"] for r in recs)
    if steps <= 0:
        return None
    return 100.0 * sum(r.attrs.get("tier_dense", 0) for r in recs) / steps
