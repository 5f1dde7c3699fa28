"""The reader of ``layout_sweep_pct``: the share of the window's repairing
steps whose sweeps ran on the sorted edge layout.

On a synthetic ring (values worked out by hand), on a ring that dropped
part of the window (None), on a program without telemetry or whose
``repair.step`` events carry no ``layout`` (None), and on the ring a tiny
churn run forced onto the layout leaves (100)."""
from __future__ import annotations

import builtins

import pytest

from bench import run as bench_run
from bench import trace
from bench.tests import tiny
from bench.tests.test_bench_spans import rec, ring, window_run
from repro import telemetry

NAME = "layout_sweep_pct"


def step(i, t_ms, layout, tier="full"):
    return rec(i, "repair.step", t_ms, t_ms, tier=tier, region_v=9,
               region_e=9, reach_rounds=3, scc_rounds=4, layout=layout)


# the window runs from 1,000 ms to 2,000 ms (two requests); of its three
# repairing steps two ran on the layout; the gated step, the steps outside
# the window and the other records must not count
RING = [step(1, 900, "fallback"),                 # before the window
        rec(2, "wal.append", 1000, 1004),
        step(3, 1100, "sorted"), step(4, 1300, "fallback"),
        step(5, 1500, "none", tier="skipped"),
        step(6, 1900, "sorted"),
        step(7, 2100, "fallback"),                # after the window
        rec(8, "broker.flush", 1400, 1410)]
WANT = 200.0 / 3


def read(run):
    return bench_run.metric_reader(NAME)(run)


def test_reader_on_synthetic_ring(monkeypatch):
    ring(monkeypatch, RING)
    assert read(window_run()) == pytest.approx(WANT)


def test_reader_none_when_the_ring_dropped_part_of_the_window(monkeypatch):
    # the oldest kept record ended inside the window: what the ring
    # dropped may have started there too
    ring(monkeypatch, RING[1:], dropped=5)
    assert read(window_run()) is None
    # the oldest kept record ended before the window: nothing of it lost
    ring(monkeypatch, RING, dropped=5)
    assert read(window_run()) == pytest.approx(WANT)


def test_reader_none_when_nothing_qualifies(monkeypatch):
    ring(monkeypatch, [r for r in RING if r.name == "broker.flush"])
    assert read(window_run()) is None
    # only gated steps in the window: none repaired
    ring(monkeypatch, [step(1, 1500, "none", tier="skipped")])
    assert read(window_run()) is None
    ring(monkeypatch, RING)
    assert read(window_run(3.0, 4.0)) is None


def test_reader_none_without_program_telemetry(monkeypatch):
    """A checkout older than the telemetry module reads nothing and
    raises nothing."""
    real = builtins.__import__

    def no_telemetry(mod, globals=None, locals=None, fromlist=(), level=0):
        if mod == "repro" and "telemetry" in (fromlist or ()):
            raise ImportError("no repro.telemetry")
        return real(mod, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_telemetry)
    assert read(window_run()) is None


def test_reader_none_on_events_without_the_field(monkeypatch):
    """A program whose ``repair.step`` events predate ``layout`` (the
    parent of the layout) reads nothing and raises nothing."""
    bare = [rec(r.id, r.name, r.t0_ns / 1e6, r.t1_ns / 1e6,
                **{k: v for k, v in r.attrs.items() if k != "layout"})
            for r in RING]
    ring(monkeypatch, bare)
    assert read(window_run()) is None


def test_reader_on_a_tiny_run_on_the_layout(monkeypatch):
    r = tiny.resolved("web_google.churn")
    r["config"]["engine"]["sparse_impl"] = "sorted"
    seen = {}

    def capture(orig):
        def end_to_end(records, *args):
            seen["records"] = records
            return orig(records, *args)
        return end_to_end

    with tiny.patched(bench_run, "end_to_end", capture):
        res = tiny.run(r)
    assert res["correct"], res["checks"]
    records, (recs, total) = seen["records"], telemetry.records()
    ring(monkeypatch, recs, total - len(recs))
    summary = trace.Summary(1.0)
    summary.programs["jit__apply_batch_impl"] = [1.0, 1]
    run = bench_run.RunData({}, {}, {}, summary, {}, {}, records, "cpu")
    assert read(run) == 100.0
    # the same ring with its oldest records dropped, the first kept one
    # inside the window
    t0 = min(q.t_submit for q in records) * 1e9
    first = next(i for i, q in enumerate(recs) if q.t1_ns >= t0)
    ring(monkeypatch, recs[first:], total - len(recs) + first + 1)
    assert read(run) is None
