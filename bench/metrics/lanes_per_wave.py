"""Tenant chunks per admission wave: how many lanes share a dispatch.

From the admission queue's ``stats()``: chunks admitted over waves
flushed in the window.  Moves ``update_ops_s``: more lanes per wave is
fewer dispatches per op."""


def read(run):
    waves = run.delta("waves")
    if waves <= 0:
        return None
    return run.delta("lanes") / waves
