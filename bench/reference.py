"""The plain reference: a host replay of the update semantics, and scipy.

It owes nothing to the code under test.  ``HostGraph`` replays each
batch as one sequential history: RemoveVertex, RemoveEdge, AddVertex,
AddEdge, ties going to the lowest lane, a vertex removal dropping its
incident edges.  Strong components come from scipy's
``connected_components(connection="strong")``, each labelled by its
minimum member id, with the sentinel ``nv`` on dead slots.
"""
from __future__ import annotations

import numpy as np

from bench.generators import ADD_EDGE, ADD_VERTEX, REM_EDGE, REM_VERTEX


def _first_lanes(cand: np.ndarray, key: np.ndarray) -> np.ndarray:
    """The lowest candidate lane of every distinct key wins."""
    idx = np.nonzero(cand)[0]
    _, first = np.unique(key[idx], return_index=True)
    win = np.zeros(cand.shape[0], bool)
    win[idx[first]] = True
    return win


class HostGraph:
    """Alive vertex slots and the live edge set as sorted ``u * nv + v``
    keys."""

    def __init__(self, nv: int, alive: np.ndarray, keys: np.ndarray):
        self.nv = nv
        self.alive = np.asarray(alive, bool).copy()
        self.keys = np.unique(np.asarray(keys, np.int64))

    def has(self, key: np.ndarray) -> np.ndarray:
        if self.keys.size == 0:
            return np.zeros(key.shape, bool)
        i = np.minimum(np.searchsorted(self.keys, key), self.keys.size - 1)
        return self.keys[i] == key

    def apply(self, kind, u, v) -> np.ndarray:
        """Apply one batch; returns the per-op acknowledgements."""
        nv = self.nv
        kind = np.asarray(kind)
        u = np.asarray(u, np.int64)
        v = np.asarray(v, np.int64)
        edge_op = (kind == ADD_EDGE) | (kind == REM_EDGE)
        in_range = (u >= 0) & (u < nv) & np.where(
            edge_op, (v >= 0) & (v < nv), True)
        uc = np.clip(u, 0, nv - 1)
        vc = np.clip(v, 0, nv - 1)
        key = uc * nv + vc
        ok = np.zeros(kind.shape[0], bool)

        win = _first_lanes((kind == REM_VERTEX) & in_range
                           & self.alive[uc], uc)
        ok |= win
        if win.any():
            killed = np.zeros(nv, bool)
            killed[uc[win]] = True
            self.alive &= ~killed
            self.keys = self.keys[~(killed[self.keys // nv]
                                    | killed[self.keys % nv])]

        ends = self.alive[uc] & self.alive[vc]
        win = _first_lanes((kind == REM_EDGE) & in_range & ends
                           & self.has(key), key)
        ok |= win
        if win.any():
            gone = np.searchsorted(self.keys, np.unique(key[win]))
            self.keys = np.delete(self.keys, gone)

        win = _first_lanes((kind == ADD_VERTEX) & in_range
                           & ~self.alive[uc], uc)
        ok |= win
        self.alive[uc[win]] = True

        ends = self.alive[uc] & self.alive[vc]
        win = _first_lanes((kind == ADD_EDGE) & in_range & ends
                           & ~self.has(key), key)
        ok |= win
        if win.any():
            new = np.unique(key[win])
            self.keys = np.insert(self.keys,
                                  np.searchsorted(self.keys, new), new)
        return ok

    def labels(self) -> np.ndarray:
        """Strong components, each labelled by its minimum member id; the
        sentinel ``nv`` for dead slots."""
        from scipy.sparse import csr_matrix
        from scipy.sparse.csgraph import connected_components
        nv = self.nv
        s, d = self.keys // nv, self.keys % nv
        g = csr_matrix((np.ones(s.size, np.int8), (s, d)), shape=(nv, nv))
        _, comp = connected_components(g, directed=True,
                                       connection="strong")
        order = np.argsort(comp, kind="stable")
        starts = np.r_[0, np.nonzero(np.diff(comp[order]))[0] + 1]
        min_id = np.empty(comp.max() + 1, np.int64)
        min_id[comp[order[starts]]] = order[starts]
        lab = min_id[comp]
        lab[~self.alive] = nv
        return lab


def same_scc(host: HostGraph, lab: np.ndarray, u, v) -> np.ndarray:
    """SameSCC answers: both endpoints alive and in one component."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    ok = (u >= 0) & (u < host.nv) & (v >= 0) & (v < host.nv)
    uc = np.clip(u, 0, host.nv - 1)
    vc = np.clip(v, 0, host.nv - 1)
    return ok & host.alive[uc] & host.alive[vc] & (lab[uc] == lab[vc])


def community_of(host: HostGraph, lab: np.ndarray, u) -> np.ndarray:
    """CommunityOf answers: the component label, ``nv`` when absent."""
    u = np.asarray(u, np.int64)
    ok = (u >= 0) & (u < host.nv)
    return np.where(ok, lab[np.clip(u, 0, host.nv - 1)], host.nv)


def state_gaps(host: HostGraph, alive: np.ndarray, ccid: np.ndarray,
               live_keys: np.ndarray, n_ccs: int) -> dict:
    """How far a device state is from the host's: mismatching alive
    slots, edges in one set and not the other, SCC labels, and the
    component count."""
    lab = host.labels()
    dev = np.unique(np.asarray(live_keys, np.int64))
    reps = int(np.sum(host.alive & (lab == np.arange(host.nv))))
    return {
        "alive_differ": int(np.sum(np.asarray(alive, bool) != host.alive)),
        "edges_differ": int(np.setxor1d(dev, host.keys,
                                        assume_unique=True).size),
        "labels_differ": int(np.sum(np.asarray(ccid, np.int64) != lab)),
        "n_ccs_differ": abs(int(n_ccs) - reps),
    }
