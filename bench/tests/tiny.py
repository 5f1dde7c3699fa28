"""Each cell cut to a size the CPU runs in seconds, for the tests.

The shapes keep what the cell exercises (bucketed chunks, free vertex
slots, a durable store reopened cold, many tenants under vmap, reads and
updates) at a few hundred vertices, so a whole run -- set-up, window, reference check -- takes
seconds on the CPU.  Nothing here runs in a benchmark run.
"""
from __future__ import annotations

import contextlib
import time
from pathlib import Path

from bench import run as bench_run

ROOT = Path(__file__).resolve().parents[2]


def resolved(cell: str) -> dict:
    r = bench_run.resolve(bench_run.load_spec(ROOT), cell, ROOT)
    c, t = r["config"], r["traffic"]
    if c["stack"] == "durable_service":
        c.update(vertices=200, vertex_slots=256, edge_slots=2048,
                 edges=600)
        c["service"]["buckets"] = [64]
        t["requests"][0]["ops"] = 64
        t["pool"] = 8
    else:
        c.update(tenants=5, vertices=40, vertex_slots=64, edge_slots=256,
                 edges=100)
        c["service"].update(buckets=[16, 64], tenant_batches=[1, 2, 4])
        t.update(sessions=4, pool=64)
        for req in t["requests"]:
            if req["kind"] == "read":
                req["queries"] = {k: 4 for k in req["queries"]}
            else:
                req["ops"] = 16
    return r


def control(r: dict) -> dict:
    """The cell with its configuration's control switched on."""
    r["config"]["engine"].update(r["config"]["control"]["engine"])
    return r


def run(r: dict, seed: int = 2 ** 40 + 17, seconds: float = 1.5) -> dict:
    return bench_run.run_cell(r, seed, seconds, False,
                              t_process=time.perf_counter())


@contextlib.contextmanager
def patched(obj, name: str, make):
    """Replace ``obj.name`` by ``make(original)`` for the block."""
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)
