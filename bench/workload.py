"""The one general generator of request streams, driven by a traffic file.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

* ``sessions``: closed-loop client sessions, one thread each: a session
  sends its next request when the last one is answered;
* ``pool``: requests drawn per session; a session that uses them all
  starts again from its first;
* ``requests``: the request kinds with their ``share``, exact in every
  block of consecutive requests: ``update`` with ``ops`` per request,
  ``read`` with point ``queries`` per kind;
* ``tenant_popularity``: ``zipf_s`` over the configuration's graphs
  (one graph: always graph 0);
* ``update_mix``: ``insert_share``, ``vertex_share`` and where endpoints
  come from (``rmat`` or ``uniform``); an edge removal names an edge of
  the graph as loaded.

Every session's pool is a pure function of (seed, session): the same
seed gives the same requests.
"""
from __future__ import annotations

import dataclasses
import fractions
import math

import numpy as np

from bench import generators as gen


@dataclasses.dataclass
class Request:
    """One client request: an update batch or a group of point reads."""
    kind: str                      # "update" | "read"
    graph: int                     # graph (tenant) index
    ops: tuple = ()                # update: (kind, u, v) int32 arrays
    queries: dict = dataclasses.field(default_factory=dict)
    # read: {"same_scc": (u, v), "community_of": (u,)}

    @property
    def size(self) -> int:
        if self.kind == "update":
            return int(self.ops[0].shape[0])
        return sum(int(q[0].shape[0]) for q in self.queries.values())


class GraphShape:
    """What the generator needs to know of one graph of a configuration."""

    def __init__(self, nv: int, abcd, perm: np.ndarray,
                 loaded_src: np.ndarray, loaded_dst: np.ndarray):
        self.nv = nv                   # vertex slots
        self.abcd = tuple(abcd)
        self.perm = perm
        self.loaded_src = loaded_src
        self.loaded_dst = loaded_dst


def _endpoints(rng, n: int, how: str, g: GraphShape):
    if how == "rmat":
        return gen.rmat_vertices(rng, n, g.abcd, g.perm)
    if how == "uniform":
        return (rng.integers(0, g.nv, n).astype(np.int32),
                rng.integers(0, g.nv, n).astype(np.int32))
    raise ValueError(f"unknown endpoint source {how!r}")


def update_ops(rng, n: int, mix: dict, g: GraphShape) -> tuple:
    """One update batch of ``n`` ops under the traffic's ``update_mix``."""
    is_add = rng.random(n) < mix["insert_share"]
    is_vertex = rng.random(n) < mix["vertex_share"]
    kind = np.where(is_add,
                    np.where(is_vertex, gen.ADD_VERTEX, gen.ADD_EDGE),
                    np.where(is_vertex, gen.REM_VERTEX, gen.REM_EDGE)
                    ).astype(np.int32)
    u, v = _endpoints(rng, n, mix["edge_endpoints"], g)
    vu, _ = _endpoints(rng, n, mix["vertex_endpoints"], g)
    u = np.where(is_vertex, vu, u).astype(np.int32)
    v = np.where(is_vertex, 0, v).astype(np.int32)
    rem = kind == gen.REM_EDGE
    if g.loaded_src.size:
        pick = rng.integers(0, g.loaded_src.size, int(rem.sum()))
        u[rem] = g.loaded_src[pick]
        v[rem] = g.loaded_dst[pick]
    return kind, u, v


def read_queries(rng, spec: dict, g: GraphShape) -> dict:
    """Point reads with R-MAT endpoints, so they land on the hubs."""
    out = {}
    for kind, n in spec.items():
        u, v = gen.rmat_vertices(rng, int(n), g.abcd, g.perm)
        out[kind] = (u, v) if kind == "same_scc" else (u,)
    return out


def request_kinds(rng, shares, n: int) -> np.ndarray:
    """Indices into ``shares`` for ``n`` requests.  Every block of
    consecutive requests holds each kind in its exact share, in a seeded
    order: a session sends the same mix whatever the seed, so the count of
    updates in a window does not swing with binomial noise."""
    fracs = [fractions.Fraction(s).limit_denominator(1000)
             for s in np.asarray(shares, np.float64) / np.sum(shares)]
    block = math.lcm(*(f.denominator for f in fracs))
    base = np.repeat(np.arange(len(fracs)),
                     [int(f * block) for f in fracs])
    return np.concatenate([rng.permutation(base)
                           for _ in range(-(-n // block))])[:n]


def session_pool(traffic: dict, graphs: list, seed: int, session: int
                 ) -> list:
    """The ``pool`` requests of one session, in the order it sends them."""
    rng = gen.rng_for(seed, 100, session)
    kinds = traffic["requests"]
    pick = request_kinds(rng, [k["share"] for k in kinds], traffic["pool"])
    if len(graphs) > 1:
        s = traffic["tenant_popularity"]["zipf_s"]
        rank_to_graph = gen.rng_for(seed, 101).permutation(len(graphs))
        ranks = rng.choice(len(graphs), traffic["pool"],
                           p=gen.zipf_weights(len(graphs), s))
        which = rank_to_graph[ranks]
    else:
        which = np.zeros(traffic["pool"], np.int64)
    pool = []
    for k, gi in zip(pick, which):
        spec = kinds[k]
        g = graphs[gi]
        if spec["kind"] == "update":
            pool.append(Request("update", int(gi), ops=update_ops(
                rng, spec["ops"], traffic["update_mix"], g)))
        elif spec["kind"] == "read":
            pool.append(Request("read", int(gi), queries=read_queries(
                rng, spec["queries"], g)))
        else:
            raise ValueError(f"unknown request kind {spec['kind']!r}")
    return pool

