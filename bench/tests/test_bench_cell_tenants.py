"""``ego_twitter_tenants.mixed`` at a tiny size on the CPU: a sound run
is correct with nothing compiled in its window, and the control and each
fault the cell can have make ``correct`` false."""
from __future__ import annotations

import pytest

from bench.tests import tiny

CELL = "ego_twitter_tenants.mixed"


def _failed(result):
    return {k for k, c in result["checks"].items()
            if c["value"] > c["limit"]}


def test_sound_run_is_correct():
    res = tiny.run(tiny.resolved(CELL))
    assert res["correct"], res["checks"]
    assert res["_compiles_in_window"] == 0
    assert res["failed"] == 0
    for name in ("update_ops_s", "update_p95_ms", "read_p95_ms",
                 "setup_s"):
        assert res["metrics"][name]["value"] > 0, name


def test_control_is_not_correct():
    res = tiny.run(tiny.control(tiny.resolved(CELL)))
    assert not res["correct"]
    assert "labels_differ" in _failed(res)


def _state_unchanged(orig):
    def scan(states, ops, cfg):
        _, ok, ovf, rstats = orig(states, ops, cfg)
        return states, ok, ovf, rstats
    return scan


def _half_batch(orig):
    def scan(states, ops, cfg):
        from repro.core import dynamic
        b = ops.kind.shape[-1]
        kind = ops.kind.at[..., b // 2:].set(dynamic.NOP)
        return orig(states, ops._replace(kind=kind), cfg)
    return scan


def _answer_altered(orig):
    def community_of_on(state, cfg, u):
        lab = orig(state, cfg, u)
        lab[0] = (lab[0] + 1) % (cfg.n_vertices + 1)
        return lab
    return community_of_on


@pytest.mark.parametrize("module,name,fault,caught", [
    ("repro.tenancy.engine", "_vmapped_scan", _state_unchanged,
     "labels_differ"),
    ("repro.tenancy.engine", "_vmapped_scan", _half_batch, "acks_differ"),
    ("repro.core.service", "community_of_on", _answer_altered,
     "answers_differ"),
])
def test_fault_is_caught(module, name, fault, caught):
    import importlib
    with tiny.patched(importlib.import_module(module), name, fault):
        res = tiny.run(tiny.resolved(CELL))
    assert not res["correct"]
    assert caught in _failed(res)
