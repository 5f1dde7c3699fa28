"""The trace reduction and the roofline arithmetic on synthetic events."""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import pytest

from bench import peaks, trace
from bench import run as bench_run


class Ev(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    stats: tuple = ()


class Line(NamedTuple):
    name: str
    events: list


class Plane(NamedTuple):
    name: str
    lines: list


# event names as a v5e trace gives them: the HLO instruction itself
FRONTIER_HLO = ("%segment_min_i32.20 = s32[4,8,1024]{2,1,0:T(1,128)S(1)} "
                "custom-call(s32[4,1,4096]{2,1,0:T(1,128)S(1)} %p0, "
                "s32[4,8,4096]{2,1,0:T(1,128)S(1)} %p1), "
                "custom_call_target=\"tpu_custom_call\"")
PROBE_HLO = ("%probe_sweep.4 = (s32[1,128]{1,0:T(1,128)S(1)}, "
             "s32[1,128]{1,0:T(1,128)S(1)}, s32[1,128]{1,0:T(1,128)S(1)}) "
             "custom-call(s32[1,128]{1,0:T(1,128)S(1)} %copy-done.7, "
             "s32[1,4096]{1,0} %p1), custom_call_target=\"tpu_custom_call\"")
# consumers of a kernel's output name it too, but are no kernel call
CONSUMER = ("%xor_compare_fusion.2 = pred[4,8,1024]{2,1,0} fusion("
            "s32[4,8,1024]{2,1,0} %segment_min_i32.20), kind=kLoop")


def _planes():
    ops = [Ev("fusion.1", 0, 100),
           Ev("fusion.2", 50, 100),            # overlaps the first
           Ev(FRONTIER_HLO, 400, 200),
           Ev(CONSUMER, 600, 0),
           Ev(PROBE_HLO, 1000, 50),
           Ev("fusion.1", 1900, 100)]
    mods = [Ev("jit__vmapped_scan(12)", 0, 600),
            Ev("jit_check_scc(3)", 1000, 50),
            Ev("jit__vmapped_scan(12)", 1900, 100)]
    host = [Ev("bench.window", 0, 2000),
            Ev("bench.update", 0, 1900),
            Ev("PjitFunction(check_scc)", 650, 300),
            Ev("bench.read", 1100, 700)]
    return [Plane("/device:TPU:0", [Line("XLA Ops", ops),
                                     Line("XLA Modules", mods)]),
            Plane("/host:CPU", [Line("python3", host)])]


def test_union_merges_overlaps_and_keeps_gaps():
    s, e = trace.merged(np.array([0.0, 50.0, 400.0]),
                        np.array([100.0, 150.0, 600.0]))
    assert s.tolist() == [0.0, 400.0] and e.tolist() == [150.0, 600.0]
    s, e = trace.merged(np.array([5.0, 0.0]), np.array([9.0, 6.0]))
    assert s.tolist() == [0.0] and e.tolist() == [9.0]


def test_reduce_busy_programs_kernels_and_gaps():
    s = trace.reduce(_planes(), window_s=2000e-9)
    # busy: [0,150] + [400,600] + [1000,1050] + [1900,2000]
    assert s.busy_s == pytest.approx(500e-9)
    assert s.program("_vmapped_scan") == (pytest.approx(700e-9), 2)
    assert s.program("check_scc") == (pytest.approx(50e-9), 1)
    assert s.ops["fusion.1"] == pytest.approx(200e-9)
    assert {k: len(v) for k, v in s.kernel_calls.items()} == {
        "frontier_min": 1, "probe": 1}
    # gaps: 150-400, 600-1000, 1050-1900; the longest is named by the
    # innermost host event that covers it most, never the window span
    assert s.gaps[0] == ("bench.read", pytest.approx(850e-9))
    assert s.gaps[1] == ("PjitFunction(check_scc)", pytest.approx(400e-9))
    assert s.gaps[2] == ("bench.update", pytest.approx(250e-9))
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "jit__vmapped_scan"
    assert len(bd["idle_gaps"]) == 3


def test_no_device_plane_reads_nothing():
    s = trace.reduce([p for p in _planes() if p.name.startswith("/host")],
                     window_s=1.0)
    assert s.busy_s is None and s.programs == {} and s.gaps == []


def test_operand_shapes_from_hlo():
    assert trace.operand_shapes(FRONTIER_HLO) == [
        (4, 8, 1024), (4, 1, 4096), (4, 8, 4096)]
    assert trace.operand_shapes(PROBE_HLO)[0] == (1, 128)


def test_least_bytes_and_peaks():
    assert peaks.frontier_min_bytes((1, 4096), (8, 4096), (8, 1024)) == \
        4 * (4096 + 8 * 4096 + 8 * 1024)
    assert peaks.probe_bytes((1, 128)) == 128 * 26
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def _run_data(summary, before=None, after=None):
    return bench_run.RunData({}, {}, {}, summary, before or {},
                             after or {}, [], "TPU v5 lite")


def test_metric_readers_on_synthetic_trace():
    s = trace.reduce(_planes(), window_s=2000e-9)
    data = _run_data(s, {"waves": 10, "lanes": 30},
                     {"waves": 12, "lanes": 37})
    read = bench_run.metric_reader
    assert read("device_idle_pct")(data) == pytest.approx(75.0)
    assert read("wave_device_ms")(data) == pytest.approx(700e-9 * 1e3 / 2)
    assert read("lanes_per_wave")(data) == pytest.approx(3.5)
    assert read("query_device_ms")(data) == pytest.approx(50e-9 * 1e3)
    need = peaks.frontier_min_bytes((4, 1, 4096), (4, 8, 4096),
                                    (4, 8, 1024))
    assert read("frontier_expand_roofline")(data) == pytest.approx(
        100 * need / 819e9 / 200e-9)
    assert read("hash_probe_roofline")(data) == pytest.approx(
        100 * peaks.probe_bytes((1, 128)) / 819e9 / 50e-9)


def test_metric_readers_return_nothing_without_a_trace():
    data = _run_data(None, {"waves": 0, "lanes": 0,
                            "steps": {"full": 0, "skipped": 0}},
                     {"waves": 0, "lanes": 0,
                      "steps": {"full": 0, "skipped": 0}})
    for name in ("device_idle_pct", "step_device_ms", "full_tier_pct",
                 "wave_device_ms", "lanes_per_wave",
                 "frontier_expand_roofline", "hash_probe_roofline",
                 "query_device_ms"):
        assert bench_run.metric_reader(name)(data) is None, name
