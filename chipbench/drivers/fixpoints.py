"""Whole batch fixpoints of the configuration's program on one seeded
EDB, back to back, one at a time from one host thread."""
from __future__ import annotations

import numpy as np

from chipbench import drive as D
from chipbench import trace as T

# The engine's export slices each relation to its row count, a shape
# that only the same data repeats; so the warm-up is a whole fixpoint
# on the cell's own EDB.
WARMUP_FIXPOINTS = 1
# A traced run records the window's first fixpoint: every fixpoint does
# the same work.
TRACED_STEPS = 1


class Driver:
    def __init__(self, cell, seed: int):
        self.cell, self.config = cell, cell.config
        self.graph = cell.generator.generate(self.config, seed)
        self.edbs = D.edbs(self.config, self.graph)
        self.outputs: list = []

    def setup(self, counter: D.CompileCounter) -> None:
        self.engine = D.engine(self.config)
        with T.span("warmup"):
            for _ in range(WARMUP_FIXPOINTS):
                self.engine.run(self.edbs)

    def step(self) -> int:
        with T.span("fixpoint"):
            out, _ = self.engine.run(self.edbs)
        with T.span("check-bookkeeping"):
            self.outputs.append(out[self.config["output"]])
        return sum(len(v) for v in self.edbs.values())

    def release(self) -> None:
        del self.engine

    def stand_in(self, steps: int) -> None:
        self.outputs = [None] * steps

    def expected(self) -> list[np.ndarray]:
        want = self.cell.reference.answer(self.edbs, self.graph.vertices)
        return [want] * len(self.outputs)

    def control(self) -> list[np.ndarray]:
        """The reference's fixpoint stopped one round before it is
        complete, for every output."""
        _, rounds = self.cell.reference.rounds(self.edbs, self.graph.vertices)
        cut, _ = self.cell.reference.rounds(self.edbs, self.graph.vertices,
                                            rounds - 1)
        return [cut] * len(self.outputs)
