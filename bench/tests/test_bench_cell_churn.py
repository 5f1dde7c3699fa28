"""``web_google.churn`` at a tiny size on the CPU: a sound run is
correct with nothing compiled in its window, and the control and each
fault the cell can have, in the step or in durability, make ``correct``
false."""
from __future__ import annotations

import numpy as np
import pytest

from bench.tests import tiny

CELL = "web_google.churn"


def _failed(result):
    return {k for k, c in result["checks"].items()
            if c["value"] > c["limit"]}


def test_sound_run_is_correct():
    res = tiny.run(tiny.resolved(CELL))
    assert res["correct"], res["checks"]
    assert res["_compiles_in_window"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["update_p95_ms"]["value"] > 0
    assert "recovered_labels_differ" in res["checks"]
    assert res["checks"]["acks_before_fsync"]["value"] == 0
    assert list(res)[-2:] == ["checks", "_compiles_in_window"]


def test_control_is_not_correct():
    res = tiny.run(tiny.control(tiny.resolved(CELL)))
    assert not res["correct"]
    assert "labels_differ" in _failed(res)


def _state_unchanged(orig):
    def step(state, ops, cfg, *, donate=False):
        _, ok, ovf, rstats = orig(state, ops, cfg, donate=False)
        return state, ok, ovf, rstats
    return step


def _half_batch(orig):
    def step(state, ops, cfg, *, donate=False):
        from repro.core import dynamic
        b = ops.kind.shape[-1]
        kind = ops.kind.at[..., b // 2:].set(dynamic.NOP)
        return orig(state, ops._replace(kind=kind), cfg, donate=donate)
    return step


def _ack_flipped(orig):
    def step(state, ops, cfg, *, donate=False):
        new, ok, ovf, rstats = orig(state, ops, cfg, donate=donate)
        return new, ok.at[..., 0].set(~ok[..., 0]), ovf, rstats
    return step


@pytest.mark.parametrize("fault,caught", [
    (_state_unchanged, "acks_differ"),
    (_half_batch, "acks_differ"),
    (_ack_flipped, "acks_differ"),
])
def test_fault_is_caught(fault, caught):
    from repro.core import dynamic
    with tiny.patched(dynamic, "apply_batch_inflight", fault):
        res = tiny.run(tiny.resolved(CELL))
    assert not res["correct"]
    assert caught in _failed(res)
    assert np.isfinite(res["checks"][caught]["value"])


def _no_wal_append(orig):
    def append(self, gen_before, kind, u, v):
        return None
    return append


def _no_fsync(orig):
    def fs_fsync(f):
        return None
    return fs_fsync


@pytest.mark.parametrize("obj,name,fault,caught", [
    ("OpLogWriter", "append", _no_wal_append, "recovered_gen_differ"),
    (None, "fs_fsync", _no_fsync, "acks_before_fsync"),
])
def test_durability_fault_is_caught(obj, name, fault, caught):
    from repro.ckpt import oplog
    with tiny.patched(getattr(oplog, obj) if obj else oplog, name, fault):
        res = tiny.run(tiny.resolved(CELL))
    assert not res["correct"]
    assert caught in _failed(res)
