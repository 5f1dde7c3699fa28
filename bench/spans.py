"""The program's own spans and counters in a run's window.

The service records spans and events in an in-memory ring
(``repro.telemetry``), on ``time.perf_counter_ns()``: the clock of the
benchmark's ``Record.t_submit`` and ``t_done``.  The readers of the
per-layer metrics that come from inside the program keep the records
that start in the window of the run's requests, from the first
submission to the last answer, through :func:`window`.  It gives None
where there is nothing to read: a program without the telemetry module,
a ring that dropped records that may have started in the window, or no
record of the names asked for in it.
"""
from __future__ import annotations


def window(run, *names):
    """The records named ``names`` that start in ``run``'s window."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    recs, total = telemetry.records()
    return in_window(recs, total, run.records, names)


def in_window(recs: list, total: int, requests: list, names) -> list | None:
    """Records of ``names`` among ``recs`` (a ring's contents, oldest
    first, of ``total`` ever recorded) that start between the first
    submission and the last answer of ``requests``."""
    if not requests or not recs:
        return None
    t0 = min(r.t_submit for r in requests) * 1e9
    t1 = max(r.t_done for r in requests) * 1e9
    # records are appended as they end: the dropped ones ended before
    # the oldest kept one, so they all started before t0 only if it did
    if total > len(recs) and recs[0].t1_ns >= t0:
        return None
    out = [r for r in recs if r.name in names and t0 <= r.t0_ns <= t1]
    return out or None


def mean_ms(recs: list) -> float:
    """Mean duration of spans, in milliseconds."""
    return sum(r.t1_ns - r.t0_ns for r in recs) / len(recs) / 1e6


def rounds(recs: list) -> int:
    """FW/BW and tier fixpoint iterations of ``repair.step`` events."""
    return sum(r.attrs["reach_rounds"] + r.attrs["scc_rounds"]
               for r in recs)
