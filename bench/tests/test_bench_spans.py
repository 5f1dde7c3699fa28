"""The readers of the program's spans and counters.

Each reader on a synthetic ring (values worked out by hand), on a ring
that dropped part of the window (None), on a program without telemetry
(None), and on the ring a tiny run of its cell leaves (a value)."""
from __future__ import annotations

import builtins
import types

import pytest

from bench import run as bench_run
from bench import spans, trace
from bench.tests import tiny
from repro import telemetry

CHURN, TENANTS = "web_google.churn", "ego_twitter_tenants.mixed"
CELL_OF = {"wal_append_ms": CHURN, "repair_rounds_per_step": CHURN,
           "round_device_ms": CHURN, "queue_wait_ms": TENANTS,
           "read_pin_ms": TENANTS, "lane_repair_pct": TENANTS}
MS = 1_000_000


def rec(i, name, t0_ms, t1_ms, **attrs):
    return telemetry.Record(i, 0, 0, name, t0_ms * MS, t1_ms * MS, 1, attrs)


def step(i, t_ms, reach, scc_):
    return rec(i, "repair.step", t_ms, t_ms, tier="full", region_v=9,
               region_e=9, reach_rounds=reach, scc_rounds=scc_)


def wave(i, t_ms, steps, skipped):
    return rec(i, "engine.wave", t_ms, t_ms, lanes=steps,
               lane_steps=steps, tier_dense=0, tier_compact=0,
               tier_full=steps - skipped, tier_skipped=skipped)


# the window runs from 1,000 ms to 2,000 ms; records outside it, or of
# other names, must not count
RING = [rec(1, "wal.append", 900, 950),            # before the window
        rec(2, "wal.append", 1000, 1004),
        rec(3, "wal.append", 1500, 1508),
        step(4, 1100, 10, 90), step(5, 1900, 30, 70),
        step(6, 2100, 500, 500),                  # after the window
        rec(7, "queue.wait", 1200, 1203),
        rec(8, "queue.wait", 1300, 1301),
        rec(9, "broker.pin", 1400, 1402.5),
        wave(10, 1600, 4, 3), wave(11, 1700, 4, 1),
        rec(12, "broker.flush", 1400, 1410)]
WANT = {"wal_append_ms": 6.0, "repair_rounds_per_step": 100.0,
        "round_device_ms": 3.0, "queue_wait_ms": 2.0, "read_pin_ms": 2.5,
        "lane_repair_pct": 50.0}


def window_run(t0_s=1.0, t1_s=2.0, device_s=0.6):
    """A run whose requests span [t0_s, t1_s], with ``device_s`` of
    update-step programs in its trace."""
    summary = trace.Summary(t1_s - t0_s)
    summary.programs["jit__apply_batch_impl"] = [device_s, 2]
    reqs = [types.SimpleNamespace(t_submit=t0_s, t_done=t0_s + 0.1),
            types.SimpleNamespace(t_submit=t1_s - 0.2, t_done=t1_s)]
    return bench_run.RunData({}, {}, {}, summary, {}, {}, reqs, "TPU v5 lite")


def ring(monkeypatch, recs, dropped=0):
    monkeypatch.setattr(telemetry, "records",
                        lambda: (list(recs), len(recs) + dropped))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_synthetic_ring(monkeypatch, name):
    ring(monkeypatch, RING)
    assert bench_run.metric_reader(name)(window_run()) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_none_when_the_ring_dropped_part_of_the_window(
        monkeypatch, name):
    # the oldest kept record ended inside the window: what the ring
    # dropped may have started there too
    ring(monkeypatch, RING[1:], dropped=5)
    assert bench_run.metric_reader(name)(window_run()) is None
    # the oldest kept record ended before the window: nothing of it lost
    ring(monkeypatch, RING, dropped=5)
    assert bench_run.metric_reader(name)(window_run()) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_none_when_nothing_qualifies(monkeypatch, name):
    ring(monkeypatch, [r for r in RING if r.name == "broker.flush"])
    assert bench_run.metric_reader(name)(window_run()) is None
    ring(monkeypatch, RING)
    assert bench_run.metric_reader(name)(window_run(3.0, 4.0)) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_none_without_program_telemetry(monkeypatch, name):
    """A checkout older than the telemetry module reads nothing and
    raises nothing."""
    real = builtins.__import__

    def no_telemetry(mod, globals=None, locals=None, fromlist=(), level=0):
        if mod == "repro" and "telemetry" in (fromlist or ()):
            raise ImportError("no repro.telemetry")
        return real(mod, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_telemetry)
    assert bench_run.metric_reader(name)(window_run()) is None


def test_in_window_keeps_records_that_start_inside():
    reqs = window_run().records
    got = spans.in_window(RING, len(RING), reqs, ("wal.append",))
    assert [r.id for r in got] == [2, 3]
    assert spans.in_window([], 0, reqs, ("wal.append",)) is None
    assert spans.in_window(RING, len(RING), [], ("wal.append",)) is None


@pytest.fixture(scope="module")
def tiny_run(request):
    """A tiny run of the cell: its requests, and the ring it left."""
    seen = {}

    def capture(orig):
        def end_to_end(records, *args):
            seen["records"] = records
            return orig(records, *args)
        return end_to_end

    with tiny.patched(bench_run, "end_to_end", capture):
        res = tiny.run(tiny.resolved(request.param))
    assert res["correct"], res["checks"]
    return request.param, seen["records"], telemetry.records()


@pytest.mark.parametrize("tiny_run,name",
                         [(CELL_OF[n], n) for n in sorted(CELL_OF)],
                         indirect=["tiny_run"])
def test_reader_on_a_tiny_run(tiny_run, monkeypatch, name):
    _, records, (recs, total) = tiny_run
    ring(monkeypatch, recs, total - len(recs))
    # no device on the CPU: one second of step programs stands in
    summary = trace.Summary(1.0)
    summary.programs["jit__apply_batch_impl"] = [1.0, 1]
    run = bench_run.RunData({}, {}, {}, summary, {}, {}, records, "cpu")
    val = bench_run.metric_reader(name)(run)
    assert val is not None and val >= 0, name
    if name == "lane_repair_pct":
        assert val <= 100
    # the same ring with its oldest records dropped, the first kept one
    # inside the window
    t0 = min(r.t_submit for r in records) * 1e9
    first = next(i for i, r in enumerate(recs) if r.t1_ns >= t0)
    ring(monkeypatch, recs[first:], total - len(recs) + first + 1)
    assert bench_run.metric_reader(name)(run) is None
