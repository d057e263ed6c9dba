"""What the traffic drivers share: the measured window, the compile
counter, and building the configuration's engine and EDBs.

A traffic file names its driver, ``drivers/<driver>.py``, which the
harness finds by name. A driver module holds ``TRACED_STEPS``, the
operations at the start of the window that a traced run records, and
one class ``Driver``, made from ``(cell, seed)``, with

* ``setup(counter)``: build the engine and warm up (counted as set-up);
* ``step()``: one operation of the window, returning the rows it
  handled;
* ``release()``: drop the engine and its device state;
* ``stand_in(steps)``: take the inputs of a window of ``steps``
  operations without running the program, for the control;
* ``expected()`` and ``control()``: the reference's answer for every
  output the window produced, and the control's.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field

from chipbench import trace as T


class CompileCounter:
    """XLA backend compiles and their seconds, from JAX's monitoring
    event (copied from the repository's chip smoke test). A program
    loaded from the persistent compilation cache does not count."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()   # compiles may report from threads
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1
                self.seconds += secs


@dataclass
class Window:
    """The measured window: one sample (start, end, rows) per operation.

    An operation starts only while the mean time of those done so far
    still fits in what is left of ``seconds``; there is always at least
    one. So a run lasts close to ``seconds`` and ends on a whole
    operation."""
    seconds: float
    start: float = 0.0
    end: float = 0.0
    samples: list = field(default_factory=list)
    traced_steps: int = 0     # operations the profiler recorded

    @property
    def length(self) -> float:
        return self.end - self.start

    def run(self, step, capture: "T.Capture | None" = None,
            traced: int = 1) -> None:
        """Call ``step()`` (which returns the rows it handled) until the
        window is full. With ``capture``, the profiler records the
        window's first ``traced`` operations, inside the host span
        ``window``, and the rest of the window runs untraced."""
        with contextlib.ExitStack() as tracing:
            if capture is not None:
                capture.start()
                tracing.callback(capture.stop)
                tracing.enter_context(T.span("window"))
            self.start = time.perf_counter()
            while True:
                t0 = time.perf_counter()
                rows = step()
                t1 = time.perf_counter()
                self.samples.append((t0, t1, rows))
                if len(self.samples) == traced:
                    tracing.close()
                done = t1 - self.start
                if done / len(self.samples) > self.seconds - done:
                    break
        self.end = time.perf_counter()
        if capture is not None:
            self.traced_steps = min(traced, len(self.samples))


def engine(config: dict, incremental: bool = False):
    """The configuration's engine, as its file states it."""
    from repro.core.optimizer import compile_program
    from repro.engine import EngineConfig, make_engine
    eng = config["engine"]
    cfg = EngineConfig(idb_cap=config["caps"]["idb_cap"],
                       intermediate_cap=config["caps"]["intermediate_cap"],
                       mode=eng["mode"], shards=eng["shards"],
                       kernel_backend=eng["kernel_backend"])
    return make_engine(compile_program(config["program"]), cfg,
                       incremental=incremental)


def edbs(config: dict, graph) -> dict:
    """The configuration's EDBs, each by the kind its file names."""
    return {name: graph.relation(kind) for name, kind in config["edbs"].items()}
