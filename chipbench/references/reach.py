"""Plain reference of ``reach(y) :- source(y). reach(y) :- reach(x),
edge(x, y).``: the vertices reachable from the source, as one column.

The configuration's graph is undirected, so the reference searches it
as such: ``answer`` is scipy's breadth-first order over the undirected
graph (the search copied from the repository's chip smoke test), the
vertices Graph500's BFS tree spans. ``rounds`` is a level-synchronous
breadth-first search that stops after a given number of levels: the
control, a fixpoint cut short, breaks the configuration's guarantee of
a complete fixpoint.
"""
from __future__ import annotations

import numpy as np


def _csr(edges: np.ndarray, n: int):
    """The undirected graph of ``edges``, as a symmetric matrix."""
    from scipy.sparse import csr_matrix
    e = np.asarray(edges, np.int64)
    return csr_matrix((np.ones(2 * len(e), np.int8),
                       (np.concatenate([e[:, 0], e[:, 1]]),
                        np.concatenate([e[:, 1], e[:, 0]]))), shape=(n, n))


def answer(edbs: dict, n: int) -> np.ndarray:
    from scipy.sparse.csgraph import breadth_first_order
    source = int(np.asarray(edbs["source"]).reshape(-1)[0])
    order = breadth_first_order(_csr(edbs["edge"], n), source,
                                directed=False, return_predecessors=False)
    return np.sort(order).astype(np.int64)[:, None]


def rounds(edbs: dict, n: int, limit: int | None = None
           ) -> tuple[np.ndarray, int]:
    """(reach after at most ``limit`` levels, levels that added a
    vertex)."""
    graph = _csr(edbs["edge"], n)
    seen = np.zeros(n, bool)
    frontier = np.unique(np.asarray(edbs["source"]).reshape(-1))
    seen[frontier] = True
    done = 0
    while len(frontier) and (limit is None or done < limit):
        nxt = np.unique(graph[frontier].indices)
        frontier = nxt[~seen[nxt]]
        if not len(frontier):
            break
        seen[frontier] = True
        done += 1
    return np.flatnonzero(seen).astype(np.int64)[:, None], done
