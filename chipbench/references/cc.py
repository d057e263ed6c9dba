"""Plain reference of the MIN-monoid weak components program
(``cc(x, MIN(i))``): every vertex on an edge with the least vertex id of
its component, the graph taken as undirected (Graphalytics WCC).

``answer`` uses scipy's connected components (copied from the
repository's chip smoke test). ``rounds`` is min-label propagation over
both edge directions that stops after a given number of rounds: the
control, a fixpoint cut short, breaks the configuration's guarantee of
a complete fixpoint.
"""
from __future__ import annotations

import numpy as np


def answer(edbs: dict, n: int) -> np.ndarray:
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components
    edges = edbs["edge"]
    graph = csr_matrix((np.ones(len(edges), np.int8),
                        (edges[:, 0], edges[:, 1])), shape=(n, n))
    _, label = connected_components(graph, directed=False)
    least = np.full(label.max() + 1, n, np.int64)
    np.minimum.at(least, label, np.arange(n))
    verts = np.unique(edges)
    return np.stack([verts, least[label[verts]]], axis=1)


def rounds(edbs: dict, n: int, limit: int | None = None
           ) -> tuple[np.ndarray, int]:
    """(labels after at most ``limit`` rounds, rounds that changed a
    label)."""
    edges = np.asarray(edbs["edge"], np.int64)
    src, dst = edges[:, 0], edges[:, 1]
    label = np.arange(n, dtype=np.int64)
    done = 0
    while limit is None or done < limit:
        new = label.copy()
        np.minimum.at(new, dst, label[src])
        np.minimum.at(new, src, label[dst])
        if np.array_equal(new, label):
            break
        label = new
        done += 1
    verts = np.unique(edges)
    return np.stack([verts, label[verts]], axis=1), done
