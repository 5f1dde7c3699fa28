"""Crash-injection suite for the durable write-ahead op log (PR-6).

Pins the durability tentpole contracts:

  * the segmented WAL round-trips typed-op records across rotations, and
    a log truncated at ANY byte offset (torn final record) yields a
    clean record *prefix* -- never garbage, never a record invented from
    partial bytes -- and ``repair_tail`` makes the store appendable
    again;
  * crash-anywhere recovery is **bit-identical**: for random typed-op
    streams, killing the store at an arbitrary WAL truncation offset or
    at any segment boundary and recovering (latest snapshot + WAL tail)
    lands exactly on some committed generation of the uninterrupted
    reference run -- same state leaves, same ``same_scc`` /
    ``community_of`` answers;
  * the two independent recovery paths agree: ``DurableService.open``
    vs :func:`repro.ckpt.durable.scratch_replay` (generation-0 snapshot
    + full log);
  * mid-snapshot crashes (torn LATEST, deleted newest npz) fall back to
    an older snapshot and converge through a longer replay;
  * ``open(to_gen=g)`` time-travels read-only to any committed
    generation;
  * a chunk whose apply fails (capacity exhausted, growth forbidden) is
    rolled back out of the WAL: recovery never replays it.

The configs are tiny and FIXED across examples/cases so the jit cache
is shared by every replay in the module.
"""
import os
import shutil
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.ckpt import checkpoint, oplog  # noqa: F401
from repro.core import dynamic
from repro.ckpt.durable import (DurableService, scratch_replay, snap_dir,
                                wal_dir)
from repro.core import graph_state as gs
from repro.core import service as svc_mod
from repro.core.service import SCCService

NV = 24
KNOBS = dict(buckets=(8,), proactive_grow=True)
QU = np.arange(8, dtype=np.int32) % NV
QV = (QU * 5 + 3) % NV


def tiny_cfg():
    return gs.GraphConfig(n_vertices=NV, edge_capacity=64, max_probes=16,
                          max_outer=NV + 1, max_inner=NV + 2)


def chunked(op_list, size=8):
    for i in range(0, len(op_list), size):
        batch = op_list[i:i + size]
        yield (np.asarray([o[0] for o in batch], np.int32),
               np.asarray([o[1] for o in batch], np.int32),
               np.asarray([o[2] for o in batch], np.int32))


def reference_run(op_list):
    """Uninterrupted in-memory run; returns the service plus the full
    per-commit history {gen: (state, cfg)} and per-chunk acks."""
    cfg = tiny_cfg()
    svc = SCCService(cfg, state=gs.all_singletons(cfg), **KNOBS)
    hist = {svc.gen: (svc.state, svc.cfg)}
    acks = []
    for kind, u, v in chunked(op_list):
        ok, gen = svc._apply_ops(kind, u, v)
        acks.append((np.asarray(ok).tolist(), gen))
        hist[svc.gen] = (svc.state, svc.cfg)
    return svc, hist, acks


def assert_state_equal(got_state, want_state, ctx=""):
    import jax
    got = jax.tree_util.tree_leaves(got_state)
    want = jax.tree_util.tree_leaves(want_state)
    assert len(got) == len(want), ctx
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), ctx


def assert_matches_reference(recovered, hist, ctx=""):
    """Recovered service sits bit-identically on SOME committed
    generation of the reference run, answers included."""
    g = recovered.gen
    assert g in hist, f"{ctx}: recovered gen {g} is not a commit point"
    ref_state, ref_cfg = hist[g]
    assert_state_equal(recovered.state, ref_state, ctx)
    assert np.array_equal(
        svc_mod.same_scc_on(recovered.state, recovered.cfg, QU, QV),
        svc_mod.same_scc_on(ref_state, ref_cfg, QU, QV)), ctx
    assert np.array_equal(
        svc_mod.community_of_on(recovered.state, recovered.cfg, QU),
        svc_mod.community_of_on(ref_state, ref_cfg, QU)), ctx
    return g


OPS_STRATEGY = st.lists(
    st.tuples(st.integers(0, 3), st.integers(0, NV - 1),
              st.integers(0, NV - 1)),
    min_size=4, max_size=40)


# ------------------------------------------------------------ WAL unit ----


def test_oplog_roundtrip_rotation_and_torn_tail(tmp_path):
    """Segmented append/read round-trip; truncation at EVERY byte offset
    of the final segment yields a clean record prefix; repair_tail makes
    the torn store appendable again."""
    d = str(tmp_path / "wal")
    rng = np.random.default_rng(0)
    w = oplog.OpLogWriter(d, segment_bytes=200, sync_every=1)
    want, gen = [], 0
    for i in range(12):
        n = int(rng.integers(1, 6))
        kind = rng.integers(0, 4, n).astype(np.int32)
        u = rng.integers(0, NV, n).astype(np.int32)
        v = rng.integers(0, NV, n).astype(np.int32)
        w.append(gen, kind, u, v)
        want.append((gen, kind.tolist(), u.tolist(), v.tolist()))
        gen += 1
        w.maybe_rotate(gen)
    w.close()
    assert len(oplog.list_segments(d)) > 2, "rotation did not happen"

    def flat(records):
        return [(r.gen_before, np.asarray(r.kind).tolist(),
                 np.asarray(r.u).tolist(), np.asarray(r.v).tolist())
                for r in records]

    assert flat(oplog.read_log(d)) == want

    last_seq, last_path = oplog.list_segments(d)[-1]
    blob = open(last_path, "rb").read()
    n_prev = len(want) - len(oplog.read_segment(last_path)[0])
    for off in range(len(blob) + 1):
        torn = str(tmp_path / "torn")
        shutil.rmtree(torn, ignore_errors=True)
        shutil.copytree(d, torn)
        tpath = os.path.join(torn, os.path.basename(last_path))
        with open(tpath, "r+b") as f:
            f.truncate(off)
        got = flat(oplog.read_log(torn))
        assert got == want[:len(got)], f"offset {off}: not a prefix"
        assert len(got) >= n_prev, f"offset {off}: lost sealed segments"
        # repair + append: the store must accept new records afterwards
        dropped = oplog.repair_tail(torn)
        assert dropped >= 0
        w2 = oplog.OpLogWriter(torn, segment_bytes=200, sync_every=1,
                               start_gen=gen)
        w2.append(gen, np.asarray([0], np.int32),
                  np.asarray([1], np.int32), np.asarray([2], np.int32))
        w2.close()
        again = flat(oplog.read_log(torn))
        assert again == got + [(gen, [0], [1], [2])], f"offset {off}"


def test_oplog_trim_keeps_coverage(tmp_path):
    """trim(min_gen) never deletes the segment that covers min_gen."""
    d = str(tmp_path / "wal")
    w = oplog.OpLogWriter(d, segment_bytes=64, sync_every=1)
    one = np.asarray([1], np.int32)
    for g in range(10):
        w.append(g, one * 3, one, one * 2)
        w.maybe_rotate(g + 1)
    w.close()
    oplog.trim(d, 7)
    records = oplog.read_log(d)
    gens = [r.gen_before for r in records]
    assert gens[0] <= 7 and gens[-1] == 9
    assert gens == list(range(gens[0], 10))


# ------------------------------------------------- crash-anywhere prop ----


@settings(max_examples=6, deadline=None)
@given(OPS_STRATEGY, st.integers(0, 10 ** 9), st.integers(0, 3))
def test_crash_replay_bit_identical(op_list, crash_seed, snap_every):
    """The tentpole property: run a random typed-op stream through a
    durable writer (tiny segments -> several rotations, optionally
    async snapshots), then crash it by (a) dropping whole tail segments
    (crash at every segment boundary) and (b) truncating the last
    remaining segment at an arbitrary byte offset (torn final record).
    Every recovery lands bit-identically on a committed generation of
    the uninterrupted reference run, and both recovery paths (latest
    snapshot + tail vs generation-0 snapshot + full log) agree."""
    base = tempfile.mkdtemp(prefix="scc-dur-")
    try:
        ref, hist, ref_acks = reference_run(op_list)
        store = os.path.join(base, "store")
        dsvc = DurableService(
            tiny_cfg(), store, state=gs.all_singletons(tiny_cfg()),
            sync_every=1, segment_bytes=192,
            snapshot_every=snap_every, snapshot_keep=10 ** 6,
            trim_on_snapshot=False, **KNOBS)
        for (kind, u, v), (want_ok, want_gen) in zip(chunked(op_list),
                                                     ref_acks):
            ok, gen = dsvc._apply_ops(kind, u, v)
            # live durable run == plain run, ack for ack
            assert np.asarray(ok).tolist() == want_ok
            assert gen == want_gen
        dsvc.close()
        assert dsvc.gen == ref.gen

        # intact recovery reaches the final generation both ways
        whole = os.path.join(base, "whole")
        shutil.copytree(store, whole)
        rec = DurableService.open(whole, snapshot_every=0)
        assert assert_matches_reference(rec, hist, "intact") == ref.gen
        scr = scratch_replay(whole)
        assert_state_equal(scr.state, rec.state, "scratch vs open")
        rec.close()

        def strip_late_snapshots(copy):
            # a store crash-cut to an earlier WAL prefix cannot contain
            # snapshots that postdate the cut: keep only the boot one
            for f in os.listdir(snap_dir(copy)):
                if f.startswith("ckpt_") and f != "ckpt_0.npz":
                    os.remove(os.path.join(snap_dir(copy), f))

        # crash at every segment boundary: only the first i segments
        # survived the crash
        segs = oplog.list_segments(wal_dir(store))
        for i in range(1, len(segs) + 1):
            cut = os.path.join(base, f"cut{i}")
            shutil.copytree(store, cut)
            strip_late_snapshots(cut)
            for seq, path in oplog.list_segments(wal_dir(cut))[i:]:
                os.remove(path)
            rec = DurableService.open(cut, snapshot_every=0)
            g = assert_matches_reference(rec, hist, f"boundary {i}")
            assert_state_equal(scratch_replay(cut, to_gen=g).state,
                               rec.state, f"boundary {i}: paths differ")
            rec.close()
            shutil.rmtree(cut)

        # torn tail: truncate the last segment at an arbitrary offset
        rng = np.random.default_rng(crash_seed)
        last_path = segs[-1][1]
        size = os.path.getsize(last_path)
        for off in {int(rng.integers(0, size + 1)) for _ in range(4)}:
            torn = os.path.join(base, f"torn{off}")
            shutil.copytree(store, torn)
            strip_late_snapshots(torn)
            with open(os.path.join(wal_dir(torn),
                                   os.path.basename(last_path)),
                      "r+b") as f:
                f.truncate(off)
            rec = DurableService.open(torn, snapshot_every=0)
            g = assert_matches_reference(rec, hist, f"torn @{off}")
            assert_state_equal(scratch_replay(torn, to_gen=g).state,
                               rec.state, f"torn @{off}: paths differ")
            rec.close()
            shutil.rmtree(torn)
    finally:
        shutil.rmtree(base, ignore_errors=True)


# ---------------------------------------------------- snapshots / misc ----


def _seed_store(base, n_chunks=6, seed=11, **durable_kw):
    rng = np.random.default_rng(seed)
    op_list = [(int(k), int(u), int(v)) for k, u, v in
               zip(rng.integers(0, 4, n_chunks * 8),
                   rng.integers(0, NV, n_chunks * 8),
                   rng.integers(0, NV, n_chunks * 8))]
    ref, hist, _ = reference_run(op_list)
    kw = dict(sync_every=1, segment_bytes=256, snapshot_every=0,
              snapshot_keep=10 ** 6, trim_on_snapshot=False)
    kw.update(durable_kw)
    dsvc = DurableService(tiny_cfg(), base,
                          state=gs.all_singletons(tiny_cfg()),
                          **kw, **KNOBS)
    for kind, u, v in chunked(op_list):
        dsvc._apply_ops(kind, u, v)
    return dsvc, ref, hist


def test_mid_snapshot_crash_falls_back(tmp_path):
    """A crash that tears the snapshot machinery (stale LATEST pointing
    at a bad npz; newest npz deleted outright) falls back to an older
    snapshot and recovers to the same final state through more WAL."""
    store = str(tmp_path / "store")
    dsvc, ref, hist = _seed_store(store)
    dsvc.snapshot_now()
    for kind, u, v in chunked([(3, 1, 2), (3, 2, 1), (1, 1, 2)] * 3):
        dsvc._apply_ops(kind, u, v)
        hist[dsvc.gen] = (dsvc.state, dsvc.cfg)
    dsvc.snapshot_now()
    dsvc.close()
    sd = snap_dir(store)
    steps = sorted(
        int(f.split("_")[1].split(".")[0]) for f in os.listdir(sd)
        if f.startswith("ckpt_") and f.endswith(".npz"))
    assert len(steps) >= 3  # boot + two manual snapshots

    # corrupt the newest snapshot's payload: LATEST checksum mismatch
    crash1 = str(tmp_path / "crash1")
    shutil.copytree(store, crash1)
    with open(os.path.join(snap_dir(crash1),
                           f"ckpt_{steps[-1]}.npz"), "r+b") as f:
        f.seek(0)
        f.write(b"\0" * 16)
    rec = DurableService.open(crash1, snapshot_every=0)
    assert rec.gen == dsvc.gen
    assert_state_equal(rec.state, hist[dsvc.gen][0], "corrupt npz")
    assert rec.replayed_wal_records > 0  # really took the longer replay
    rec.close()

    # delete the newest snapshot file entirely (LATEST now dangling)
    crash2 = str(tmp_path / "crash2")
    shutil.copytree(store, crash2)
    os.remove(os.path.join(snap_dir(crash2), f"ckpt_{steps[-1]}.npz"))
    rec = DurableService.open(crash2, snapshot_every=0)
    assert rec.gen == dsvc.gen
    assert_state_equal(rec.state, hist[dsvc.gen][0], "deleted npz")
    rec.close()


def test_time_travel_open_to_gen(tmp_path):
    """open(to_gen=g) lands read-only on the first commit >= g and is
    bit-identical to the reference run there."""
    store = str(tmp_path / "store")
    dsvc, ref, hist = _seed_store(store)
    dsvc.close()
    commits = sorted(hist)
    for g in (commits[1], commits[len(commits) // 2], commits[-1]):
        rec = DurableService.open(store, to_gen=g)
        landed = assert_matches_reference(rec, hist, f"to_gen={g}")
        assert landed >= g
        assert min(c for c in commits if c >= g) == landed
        assert rec._wal is None  # read-only: no WAL attached
        rec.close()


def test_failed_chunk_rolled_back_out_of_wal(tmp_path):
    """A chunk the service rejects wholesale (table full, growth
    forbidden) must leave no WAL record behind: recovery replays the
    accepted history only, and later appends still work."""
    cfg = gs.GraphConfig(n_vertices=NV, edge_capacity=16, max_probes=16,
                         max_outer=NV + 1, max_inner=NV + 2)
    store = str(tmp_path / "store")
    dsvc = DurableService(cfg, store, state=gs.all_singletons(cfg),
                          buckets=(8,), max_edge_capacity=16,
                          sync_every=1, snapshot_every=0)
    pairs = [(a, b) for a in range(NV) for b in range(NV) if a != b]
    one = np.full(8, dynamic.ADD_EDGE, np.int32)
    gens = [0]
    for lo in (0, 8):  # fill the 16-slot table in two committed chunks
        dsvc._apply_ops(
            one, np.asarray([p[0] for p in pairs[lo:lo + 8]], np.int32),
            np.asarray([p[1] for p in pairs[lo:lo + 8]], np.int32))
        gens.append(dsvc.gen)
    good_gen = dsvc.gen
    with pytest.raises(Exception):
        # 8 fresh edges cannot fit in a full capacity-16 table and
        # growth is forbidden: the one-chunk apply fails wholesale
        dsvc._apply_ops(one,
                        np.asarray([p[0] for p in pairs[16:24]], np.int32),
                        np.asarray([p[1] for p in pairs[16:24]], np.int32))
    assert dsvc.gen == good_gen
    assert dsvc.stats()["wal_rollbacks"] == 1
    dsvc._apply_ops(one[:1], np.asarray([pairs[9][0]], np.int32),
                    np.asarray([pairs[9][1]], np.int32))
    final_state, final_gen = dsvc.state, dsvc.gen
    dsvc.close()
    recs = oplog.read_log(wal_dir(store))
    assert [r.gen_before for r in recs] == gens
    rec = DurableService.open(store, snapshot_every=0)
    assert rec.gen == final_gen
    assert_state_equal(rec.state, final_state, "post-rollback recovery")
    rec.close()


def test_snapshot_trim_bounds_log_and_recovery_still_works(tmp_path):
    """With trim_on_snapshot, old segments disappear once a snapshot
    covers them -- and recovery (snapshot + shorter tail) still equals
    the live state."""
    store = str(tmp_path / "store")
    dsvc, ref, hist = _seed_store(store, n_chunks=10, segment_bytes=128,
                                  snapshot_every=3,
                                  trim_on_snapshot=True, snapshot_keep=3)
    if dsvc._snap_thread is not None:
        dsvc._snap_thread.join()
    dsvc.snapshot_now()
    live_state, live_gen = dsvc.state, dsvc.gen
    dsvc.close()
    recs = oplog.read_log(wal_dir(store))
    assert not recs or recs[0].gen_before > 0, "trim never dropped gen-0"
    rec = DurableService.open(store, snapshot_every=0)
    assert rec.gen == live_gen
    assert_state_equal(rec.state, live_state, "trimmed recovery")
    rec.close()
