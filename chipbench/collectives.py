"""Collective operations in a device trace, for the metrics of the
cells that run on several chips.

On a TPU each op's event is named by its HLO text, whose opcode says
what the op is whatever its name: the sharded engine's all-to-all runs
as ``%all_to_all.9 = s32[4,2097152,2]{...} all-to-all(...)``,
synchronous, named after JAX's primitive (a v5e trace of the four-chip
Reach cell, JAX 0.9). An op without text is known by its name.
"""
from __future__ import annotations

import functools
import re

from chipbench import trace as T

# HLO opcodes of collectives; an asynchronous one adds -start / -done
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")
# the opcode: the first word after the result's shape that opens "("
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


@functools.lru_cache(maxsize=1 << 16)
def _opcode(name: str, text: str) -> str:
    m = _OPCODE.search(text.partition(" = ")[2])
    return m.group(1) if m else name.split(".", 1)[0].replace("_", "-")


def opcode(op: T.Op) -> str:
    """The op's HLO opcode, from its text, else from its name."""
    return _opcode(op.name, str(op.stats.get("long_name", "")))


def is_all_to_all(op: T.Op) -> bool:
    return opcode(op) == "all-to-all"


def is_collective(op: T.Op) -> bool:
    return opcode(op).startswith(COLLECTIVES)


def result_bytes(op: T.Op) -> "int | None":
    """Bytes of the array an op delivers, from its HLO text; None where
    the trace holds no text for the op."""
    text = str(op.stats.get("long_name", ""))
    head, sep, _ = text.partition(" = ")[2].partition(f" {opcode(op)}(")
    if not sep:
        return None
    return sum(T.shape_bytes(head))


def _ran(trace: "T.Trace | None", chips: int) -> list[int]:
    """The devices the cell used; NothingToRead unless an all-to-all
    ran on the first in the window."""
    if trace is None:
        raise T.NothingToRead("the run took no trace")
    used = sorted(trace.devices)[:chips]
    if not used or not any(is_all_to_all(o)
                           for o in trace.window_ops(used[0])):
        raise T.NothingToRead("no all-to-all op ran on the first device "
                              "in the window")
    return used


def all_to_all_bytes(trace: "T.Trace | None", window, chips: int) -> float:
    """Bytes the all-to-all ops delivered on the first chip, per traced
    step: the padded send buffers, whatever their live rows."""
    first = _ran(trace, chips)[0]
    cut = trace.cut_at()
    if cut is not None:
        raise T.NothingToRead("the profiler's record was cut short, so the "
                              "traced steps are not whole")
    sizes = [result_bytes(o) for o in trace.window_ops(first)
             if is_all_to_all(o)]
    if any(b is None for b in sizes):
        raise T.NothingToRead("the trace holds no HLO text (stat "
                              "'long_name') for an all-to-all op")
    return sum(sizes) / window.traced_steps


def work_skew(trace: "T.Trace | None", chips: int) -> float:
    """The largest over the chips, over their mean, of the seconds of
    the traced window in which an op other than a collective ran: 1.0
    where every chip is as busy. A chip that waits for its peers waits
    inside a collective, so those are left out. Every chip runs one
    program over blocks of one capacity, so the sorts, bucket scatters
    and dedupes take the same time on each whatever rows they hold:
    this is the balance of padded device work, and it follows the
    hash's placement of live rows only through ops whose time depends
    on them (the Pallas probe)."""
    used = _ran(trace, chips)
    lo, hi = trace.window()
    busy = [sum(e - s for s, e in T.union(
        ((o.start_ns, o.end_ns) for o in trace.window_ops(d)
         if not is_collective(o)), lo, hi)) for d in used]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        raise T.NothingToRead("no op other than a collective ran in the "
                              "window")
    return max(busy) / mean
