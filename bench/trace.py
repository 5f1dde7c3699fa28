"""Reduce a profiler trace to what the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``: planes (one per device, one
for the host), lines, and events with a start and a duration in
nanoseconds.  On a TPU device plane the line ``XLA Ops`` holds one event
per executed operation and ``XLA Modules`` one per executed program.
This module turns that into:

* ``busy_s``: the union of the operation intervals, averaged over the
  device planes, and ``window_s``, the traced window's length;
* device seconds and executions per program (by the jitted function's
  name) and per operation;
* each call of a Pallas kernel with its HLO text, whose operand shapes
  the kernels' roofline shares read;
* the longest idle gaps, each named by the host event (the benchmark's
  own ``TraceAnnotation`` spans and JAX's dispatch events) that overlaps
  it most.

It reads plain objects with ``.planes``/``.lines``/``.events``, so the
tests feed it synthetic events.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")
_SHAPE = re.compile(r"\b(?:s32|u32|s8|u8|pred|f32|bf16|s16|s64)\[([\d,]*)\]")


def program_name(event_name: str) -> str:
    """``jit__vmapped_scan(42)`` -> ``jit__vmapped_scan``."""
    return _SUFFIX.sub("", event_name)


def merged(starts: np.ndarray, ends: np.ndarray):
    """Union of intervals as sorted, disjoint (starts, ends)."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > reach[:-1]]
    idx = np.nonzero(new)[0]
    return s[idx], np.r_[reach[idx[1:] - 1], reach[-1]]


def operand_shapes(hlo_text: str) -> list:
    """Array shapes named in an HLO instruction, in order (result first)."""
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in _SHAPE.finditer(hlo_text)]


def kernel_of(op_text: str) -> str | None:
    """Which Pallas kernel an operation runs, if any.  On a TPU the
    event's name is the HLO instruction, and a Mosaic call keeps the name
    of its jitted wrapper: ``%segment_min_i32.20 = ... custom-call(...)``."""
    if "custom-call(" not in op_text:
        return None
    head = op_text.split(" = ", 1)[0].lstrip("%")
    for kernel, wrapper in KERNELS.items():
        if head.startswith(wrapper):
            return kernel
    return None


# kernel -> the jitted wrapper whose name its Mosaic calls carry (the
# pallas_call bodies are both named ``_kernel``)
KERNELS = {
    "frontier_min": "segment_min_i32",
    "probe": "probe_sweep",
}


class Summary:
    def __init__(self, window_s: float):
        self.window_s = window_s
        self.busy_s = None            # None: no device plane in the trace
        self.programs = {}            # name -> [device seconds, count]
        self.ops = {}                 # name -> device seconds
        self.kernel_calls = {}        # kernel -> [(seconds, hlo text)]
        self.gaps = []                # [(label, seconds)], longest first

    def program(self, *names) -> tuple:
        """(device seconds, executions) of the programs whose name
        contains any of ``names``."""
        sec = cnt = 0
        for p, (s, c) in self.programs.items():
            if any(n in p for n in names):
                sec += s
                cnt += c
        return sec, cnt

    def breakdown(self) -> dict:
        top = sorted(self.programs.items(), key=lambda kv: -kv[1][0])[:10]
        return {"device_ops": [[n, s] for n, (s, _) in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def _host_events(planes):
    names, starts, ends = [], [], []
    for p in planes:
        if not p.name.startswith("/host"):
            continue
        for ln in p.lines:
            for e in ln.events:
                names.append(e.name)
                starts.append(e.start_ns)
                ends.append(e.start_ns + e.duration_ns)
    return names, np.asarray(starts, np.float64), np.asarray(ends,
                                                             np.float64)


def _label_gaps(gap_s, gap_e, planes, top: int) -> list:
    """Name each of the ``top`` longest gaps by what the host was doing:
    the shortest host event that covers at least half of the gap (else
    the one that covers most of it), never the window-wide annotation."""
    names, hs, he = _host_events(planes)
    keep = np.asarray([n != "bench.window" for n in names], bool)
    names = [n for n, k in zip(names, keep) if k]
    hs, he = hs[keep], he[keep]
    out = []
    for i in np.argsort(gap_s - gap_e)[:top]:
        a, b = gap_s[i], gap_e[i]
        ov = np.minimum(he, b) - np.maximum(hs, a)
        label = "no host event"
        if np.any(ov > 0):
            half = np.nonzero(ov >= 0.5 * (b - a))[0]
            if half.size:
                label = names[half[np.argmin(he[half] - hs[half])]]
            else:
                label = names[int(np.argmax(ov))]
        out.append((label, float(b - a) / 1e9))
    return out


def reduce(planes, window_s: float, top_gaps: int = 10) -> Summary:
    """The summary of one trace's planes (any objects shaped like
    ``jax.profiler.ProfileData``'s)."""
    planes = list(planes)
    summary = Summary(window_s)
    devices = [p for p in planes if p.name.startswith("/device:")]
    busy, gap_s, gap_e = [], [], []
    for p in devices:
        lines = {ln.name: ln for ln in p.lines}
        ops = lines.get(OPS_LINE)
        if ops is None:
            continue
        st, en = [], []
        for e in ops.events:
            st.append(e.start_ns)
            en.append(e.start_ns + e.duration_ns)
            summary.ops[e.name] = summary.ops.get(e.name, 0.0) \
                + e.duration_ns / 1e9
            k = kernel_of(e.name)
            if k is not None:
                summary.kernel_calls.setdefault(k, []).append(
                    (e.duration_ns / 1e9, e.name))
        ms, me = merged(np.asarray(st, np.float64),
                        np.asarray(en, np.float64))
        busy.append(float(np.sum(me - ms)) / 1e9)
        if ms.size > 1:
            gap_s.append(me[:-1])
            gap_e.append(ms[1:])
        mods = lines.get(MODULES_LINE)
        for e in (mods.events if mods is not None else ()):
            rec = summary.programs.setdefault(program_name(e.name),
                                              [0.0, 0])
            rec[0] += e.duration_ns / 1e9
            rec[1] += 1
    if busy:
        summary.busy_s = float(np.mean(busy))
    if gap_s:
        summary.gaps = _label_gaps(np.concatenate(gap_s),
                                   np.concatenate(gap_e), planes, top_gaps)
    return summary


def summarize(trace_dir: str, t_start: float, t_stop: float) -> Summary:
    """Read the newest trace under ``trace_dir``; the window is the host
    clock's span from the trace's start to its stop."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))
    return reduce(data.planes, t_stop - t_start)
