"""Milliseconds per WAL append of the durable writer, fsync included.

The mean of the program's ``wal.append`` spans (``ckpt/oplog.py``; the
``wal.fsync`` span nests inside) that start in the window.  Moves
``update_p95_ms``: every acknowledged chunk waits for its append."""

from bench import spans


def read(run):
    recs = spans.window(run, "wal.append")
    return None if recs is None else spans.mean_ms(recs)
