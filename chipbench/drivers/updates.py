"""A closed loop of one writer on an incrementally maintained view.

The engine is initialized on the seeded EDB in set-up. Each operation
then inserts ``batch_edges`` new undirected edges into the traffic's
``relation``, as rows of the kind the configuration gives that
relation, and waits for the view. The edges come from the generator's
own stream in the order drawn, so inserts follow the graph's degree
skew; self loops and edges already present are skipped.
"""
from __future__ import annotations

import numpy as np

from chipbench import drive as D
from chipbench import trace as T

# Warm-up batches: at least MIN, then until the compiles of a batch stop
# falling, at most MAX. They do not reach 0: the engine slices each
# relation to its row count when it exports a view, and every insert
# makes new counts.
MIN_WARMUP_BATCHES, MAX_WARMUP_BATCHES = 2, 8
# A traced run records the window's first applies.
TRACED_STEPS = 4


class Driver:
    def __init__(self, cell, seed: int):
        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.graph = cell.generator.generate(self.config, seed)
        self.relation = self.traffic["relation"]
        self.kind = self.config["edbs"][self.relation]
        self.base = D.edbs(self.config, self.graph)
        key = self.cell.generator.undirected_key(self.graph.edges)
        self._present = set(map(tuple, key.tolist()))
        self._pool: list[tuple] = []
        self._part = 0
        self.warm_batches: list[np.ndarray] = []
        self.batches: list[np.ndarray] = []     # the window's, in order
        self.outputs: list[np.ndarray] = []     # what each apply returned

    def _batch(self) -> np.ndarray:
        """The rows of ``batch_edges`` edges not yet in the graph."""
        out, fresh = [], True
        while len(out) < self.traffic["batch_edges"]:
            if not self._pool:
                if not fresh:
                    raise RuntimeError("the stream holds no new edges")
                self._part += 1
                drawn = self.graph.stream(len(self.graph.edges), self._part)
                self._pool = list(map(tuple, drawn.tolist()))[::-1]
                fresh = False
            edge = self._pool.pop()
            key = (min(edge), max(edge))
            if key[0] != key[1] and key not in self._present:
                self._present.add(key)
                out.append(edge)
                fresh = True
        return self.graph.rows(self.kind, np.array(out, np.int64))

    def _apply(self, rows: np.ndarray) -> dict:
        with T.span("apply"):
            return self.engine.apply(inserts={self.relation: rows})

    def setup(self, counter: D.CompileCounter) -> None:
        self.engine = D.engine(self.config, incremental=True)
        compiles = []
        with T.span("warmup"):
            self.engine.initialize(self.base)
            for i in range(MAX_WARMUP_BATCHES):
                c0 = counter.count
                rows = self._batch()
                self._apply(rows)
                self.warm_batches.append(rows)
                compiles.append(counter.count - c0)
                if i + 1 >= MIN_WARMUP_BATCHES and compiles[-1] >= compiles[-2]:
                    break

    def step(self) -> int:
        rows = self._batch()
        out = self._apply(rows)
        with T.span("check-bookkeeping"):
            self.batches.append(rows)
            self.outputs.append(out[self.config["output"]])
        return len(rows)

    def release(self) -> None:
        del self.engine

    def stand_in(self, steps: int) -> None:
        self.warm_batches = [self._batch() for _ in range(MIN_WARMUP_BATCHES)]
        self.batches = [self._batch() for _ in range(steps)]

    def _answers(self) -> list[np.ndarray]:
        """The reference on the relation as it stands before the window
        and after each window step (every inserted row is new, so
        concatenation is the set)."""
        rows = np.concatenate([self.base[self.relation], *self.warm_batches])
        out = []
        for batch in [rows[:0], *self.batches]:
            rows = np.concatenate([rows, batch])
            edbs = {**self.base, self.relation: rows}
            out.append(self.cell.reference.answer(edbs, self.graph.vertices))
        return out

    def expected(self) -> list[np.ndarray]:
        return self._answers()[1:]

    def control(self) -> list[np.ndarray]:
        """Each step answers with the view before its batch, one batch
        behind."""
        return self._answers()[:-1]
