"""``ego_gplus_tenants.mixed`` at a CPU size, through the ``built_tenants``
stack: a sound run is correct with nothing compiled in its window and
every repaired lane-step on the dense tier, and the control makes it
false.  Then the cell's two readers, ``dense_tier_pct`` and
``reach_blockmm_roofline``, on synthetic waves and trace operations."""
from __future__ import annotations

import time
import types

import pytest

from bench import run as bench_run
from bench import trace
from bench.tests import tiny
from repro import telemetry

CELL = "ego_gplus_tenants.mixed"
MS = 1_000_000
V5E = "TPU v5 lite"


def resolved() -> dict:
    """Five tenants of 40 vertices in 64 slots (``tiny``), each with 300
    edges (19% of the ordered pairs) and a dense block of its slots."""
    r = tiny.resolved(CELL)
    c = r["config"]
    c.update(edges=300, edge_slots=1024)
    c["engine"]["dense_capacity"] = c["vertex_slots"]
    return r


def _failed(result):
    return {k for k, c in result["checks"].items()
            if c["value"] > c["limit"]}


def test_sound_run_is_correct():
    t0 = time.perf_counter_ns()
    res = tiny.run(resolved())
    assert res["correct"], res["checks"]
    assert res["_compiles_in_window"] == 0
    assert res["failed"] == 0
    for name in ("update_ops_s", "update_p95_ms", "read_p95_ms",
                 "setup_s"):
        assert res["metrics"][name]["value"] > 0, name
    waves = [r for r in telemetry.records()[0]
             if r.name == "engine.wave" and r.t0_ns >= t0]
    assert sum(w.attrs["tier_dense"] for w in waves) > 0
    assert sum(w.attrs["tier_compact"] + w.attrs["tier_full"]
               for w in waves) == 0


def test_control_is_not_correct():
    res = tiny.run(tiny.control(resolved()))
    assert not res["correct"]
    assert "labels_differ" in _failed(res)


# ------------------------------------------------------------- readers ---


def wave(i, t_ms, steps, dense):
    return telemetry.Record(i, 0, 0, "engine.wave", t_ms * MS, t_ms * MS, 1,
                            dict(lanes=steps, lane_steps=steps,
                                 tier_dense=dense, tier_compact=0,
                                 tier_full=0, tier_skipped=steps - dense))


# the window runs from 1,000 ms to 2,000 ms; the last wave is after it
RING = [wave(1, 1100, 4, 3), wave(2, 1500, 4, 1), wave(3, 2500, 16, 16)]
KERNEL = "%bool_matmul_f32.3 = f32[4,1024,1024]{2,1,0} custom-call(%a, %b)"


def run_data(ops: dict, dense_capacity=1024, vertex_slots=1024):
    summary = trace.Summary(1.0)
    summary.ops.update(ops)
    reqs = [types.SimpleNamespace(t_submit=1.0, t_done=1.2),
            types.SimpleNamespace(t_submit=1.8, t_done=2.0)]
    config = {"vertex_slots": vertex_slots,
              "engine": {"dense_capacity": dense_capacity}}
    return bench_run.RunData({}, config, {}, summary, {}, {}, reqs, V5E)


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setattr(telemetry, "records", lambda: (list(RING),
                                                       len(RING)))


def test_dense_tier_pct_on_synthetic_waves(ring):
    read = bench_run.metric_reader("dense_tier_pct")
    assert read(run_data({})) == pytest.approx(100.0 * 4 / 8)


def test_reach_blockmm_roofline_on_synthetic_ops(ring):
    read = bench_run.metric_reader("reach_blockmm_roofline")
    # 4 dense lane-steps, each 10 squarings of 2 * 1024^3 operations
    least_s = 4 * 10 * 2 * 1024 ** 3 / 197e12
    ops = {KERNEL: 0.002,
           # other kernels and a non-Mosaic op of the same prefix
           "%segment_min_i32.7 = s32[4,1024]{1,0} custom-call(%x)": 5.0,
           "%bool_matmul_f32.9 = f32[4,1024,1024]{2,1,0} fusion(%y)": 5.0}
    assert read(run_data(ops)) == pytest.approx(100.0 * least_s / 0.002)
    # at the peak rate exactly the share is 100; it cannot read more
    # unless the time misses part of the work
    assert read(run_data({KERNEL: least_s})) == pytest.approx(100.0)
    assert read(run_data({KERNEL: 2 * least_s})) <= 100.0
    # the block is the vertex slots when the capacity is larger
    small = 4 * 6 * 2 * 64 ** 3 / 197e12
    assert read(run_data({KERNEL: 0.001}, 1024, 64)) == \
        pytest.approx(100.0 * small / 0.001)


def test_readers_read_nothing_without_their_inputs(ring):
    share = bench_run.metric_reader("reach_blockmm_roofline")
    assert share(run_data({})) is None                  # no kernel call
    assert share(run_data({KERNEL: 0.001}, dense_capacity=0)) is None
    no_trace = run_data({KERNEL: 0.001})
    no_trace.trace = None
    assert share(no_trace) is None
