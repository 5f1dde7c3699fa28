"""Share of the window's repairing steps whose sweeps ran on the sorted
edge layout.

From the program's ``repair.step`` events, one per resolved step: of the
steps the repair gate did not skip, the share whose ``layout`` is
``sorted`` -- the live edges sorted once per step, every FW/BW, trim and
propagation round a gather and a sorted reduction -- and not
``fallback`` (more than half the table's slots live, so the scatter
sweeps ran) or ``none`` (the sweeps resolve to another engine).  A
program whose events carry no ``layout`` gives nothing.  Moves
``update_p95_ms``: a scatter round over the whole table costs what the
layout's round saves."""

from bench import spans


def read(run):
    recs = spans.window(run, "repair.step")
    if recs is None:
        return None
    recs = [r for r in recs if r.attrs["tier"] != "skipped"]
    if not recs or any("layout" not in r.attrs for r in recs):
        return None
    return 100.0 * sum(r.attrs["layout"] == "sorted" for r in recs) \
        / len(recs)
