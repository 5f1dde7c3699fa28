"""Share of the window's update steps that ran the full repair tier.

From the service's ``repair_tier_steps`` counters (``stats()``), read
before and after the window.  Moves ``update_ops_s``: a full-tier step
sweeps the whole edge table."""


def read(run):
    steps = run.delta("steps")
    total = sum(steps.values())
    if total <= 0:
        return None
    return 100.0 * steps["full"] / total
