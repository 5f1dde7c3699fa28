"""Share of the tenant engine's lane-steps that needed a repair.

From the program's ``engine.wave`` events (``tenancy/engine.py``): the
real lane-steps of each wave (never its padding rows) and how many the
repair gate skipped.  Under ``vmap`` every lane pays for the repair; the
rest, 100 minus this share, is what a gate outside the ``vmap`` could
save.  Moves ``update_ops_s``."""

from bench import spans


def read(run):
    recs = spans.window(run, "engine.wave")
    if recs is None:
        return None
    steps = sum(r.attrs["lane_steps"] for r in recs)
    skipped = sum(r.attrs["tier_skipped"] for r in recs)
    if steps <= 0:
        return None
    return 100.0 * (steps - skipped) / steps
