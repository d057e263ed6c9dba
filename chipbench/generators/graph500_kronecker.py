"""Graph500 Kronecker graphs (Graph500 specification, section 3): the
generator behind LDBC Graphalytics' ``graph500-*`` data sets.

The Kronecker draws are copied from the repository's chip smoke test,
so that a change to the smoke leaves the benchmark's data as it is.
The graph is then made as Graphalytics ships it, and as Graph500's
kernel 1 may build it: undirected, with self loops and duplicate edges
dropped.

A Graphalytics data set is one fixed graph. So the graph's structure,
the Kronecker draws, comes from the configuration's
``structure_seed``, and ``--seed`` draws what the specification
permutes: the order of the edges and, where ``seeded_labels`` is true,
the vertex labels. Every seed then gives the engine the same amount of
work (the same depth of search, the same component sizes, the same new
edges in a stream) on different data. Labels stay with the structure
where the work depends on them: min-label propagation takes as many
rounds as the least label needs to spread.
"""
from __future__ import annotations

import numpy as np


def _pairs(scale: int, m: int, initiator, rng) -> tuple[np.ndarray, np.ndarray]:
    """``m`` (src, dst) pairs before the vertex permutation."""
    a, b, c, _ = initiator
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    return src, dst


def undirected_key(edges: np.ndarray) -> np.ndarray:
    """Each edge as (lower id, higher id): one key per undirected edge."""
    return np.sort(np.asarray(edges, np.int64).reshape(-1, 2), axis=1)


class Graph:
    """One undirected Kronecker graph: ``edges`` [k, 2] int64, each
    undirected edge once, ids below ``vertices``. ``stream(k, part)``
    draws further pairs from the same initiator under the same vertex
    labels, so that inserts follow the graph's own degree skew."""

    def __init__(self, params: dict, seed: int):
        self.scale = int(params["scale"])
        self.initiator = tuple(params["initiator"])
        self.structure = int(params["structure_seed"])
        self.vertices = 1 << self.scale
        m = int(params["edge_factor"]) << self.scale
        src, dst = _pairs(self.scale, m, self.initiator,
                          np.random.default_rng([self.structure, 0]))
        keep = src != dst
        base = np.unique(undirected_key(np.stack([src[keep], dst[keep]], 1)),
                         axis=0)
        self._degree = np.bincount(base.ravel(), minlength=self.vertices)
        labels = seed if params["seeded_labels"] else self.structure
        self.perm = np.random.default_rng([labels, 1]).permutation(
            self.vertices)
        order = np.random.default_rng([seed, 0]).permutation(len(base))
        self.edges = self.perm[base[order]]

    def source(self) -> int:
        """The BFS root: a vertex with an edge, drawn from the structure
        seed and carried through the labels."""
        rng = np.random.default_rng([self.structure, 2])
        return int(self.perm[rng.choice(np.flatnonzero(self._degree))])

    @staticmethod
    def rows(kind: str, edges: np.ndarray) -> np.ndarray:
        """Undirected ``edges`` as rows of a relation of ``kind``:
        ``undirected`` holds each edge once, ``symmetric`` holds it in
        both directions."""
        if kind == "undirected":
            return edges
        if kind == "symmetric":
            return np.concatenate([edges, edges[:, ::-1]])
        raise KeyError(f"graph500_kronecker makes no edge relation {kind!r}")

    def relation(self, kind: str) -> np.ndarray:
        """An EDB by the kind a configuration names: an edge relation of
        ``rows``, or ``bfs_source`` (one row, the BFS root)."""
        if kind == "bfs_source":
            return np.array([[self.source()]], np.int64)
        return self.rows(kind, self.edges)

    def stream(self, k: int, part: int) -> np.ndarray:
        """``k`` more pairs, the ``part``-th draw of this graph's
        stream, in the order drawn; self loops and edges already in the
        graph are among them, for the caller to skip."""
        rng = np.random.default_rng([self.structure, 3, part])
        src, dst = _pairs(self.scale, k, self.initiator, rng)
        return np.stack([self.perm[src], self.perm[dst]], axis=1)


def generate(params: dict, seed: int) -> Graph:
    return Graph(params, seed)
