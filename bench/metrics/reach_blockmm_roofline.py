"""Share of the bf16 MXU peak reached by the ``reach_blockmm`` kernel.

The least work of the window's dense repairs: each dense-tier lane-step
(``tier_dense`` of the ``engine.wave`` events) closes an n x n boolean
adjacency, n the dense block (the configuration's ``dense_capacity``, at
most its vertex slots), with ``closure_rounds(n)`` squarings of 2 n^3
operations each.  That work at the chip's bf16 peak is the least time it
could take; the share is that over the device time of the kernel's Mosaic
calls, found in the trace's operations by the jitted wrapper's name at the
head of their HLO text.  Padding rows, gated lanes and tile padding add
time and no counted work, so the share cannot pass 100%.  Moves
``update_ops_s``."""

import math

from bench import peaks, spans

WRAPPER = "bool_matmul_f32"


def closure_rounds(n: int) -> int:
    """Squarings that close an n x n boolean adjacency."""
    return max(1, math.ceil(math.log2(max(n, 2))))


def least_ops(dense_steps: int, n: int) -> float:
    """Operations of ``dense_steps`` closures of an n x n block."""
    return float(dense_steps) * closure_rounds(n) * 2.0 * n ** 3


def kernel_seconds(ops: dict) -> float:
    """Device seconds of the kernel's Mosaic calls among ``ops`` (HLO
    text -> seconds): ``%bool_matmul_f32.3 = ... custom-call(...)``."""
    return sum(sec for text, sec in ops.items()
               if "custom-call(" in text
               and text.split(" = ", 1)[0].lstrip("%").startswith(WRAPPER))


def read(run):
    if run.trace is None:
        return None
    sec = kernel_seconds(run.trace.ops)
    recs = spans.window(run, "engine.wave")
    if sec <= 0 or recs is None:
        return None
    dense = sum(r.attrs.get("tier_dense", 0) for r in recs)
    n = min(run.config["engine"].get("dense_capacity", 0),
            run.config["vertex_slots"])
    if dense <= 0 or n <= 0:
        return None
    flops = peaks.peak(run.device_kind)["bf16_flops"]
    return 100.0 * (least_ops(dense, n) / flops) / sec
