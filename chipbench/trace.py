"""Profiler capture of the measured window, and its reduction.

A traced run records the first operations of the window with
``jax.profiler``, inside the host span ``window``; the benchmark's own
host spans (``SPANS``) go into the same trace through
``TraceAnnotation``. ``read_xplane`` keeps what the metric readers use:
every operation on each device's ``XLA Ops`` line and the benchmark's
spans, on the profiler's one clock. The readers then take device time,
idle gaps and the host span each gap falls in from a ``Trace``.
"""
from __future__ import annotations

import re
import shutil
import tempfile
from dataclasses import dataclass, field
from pathlib import Path

SPANS = ("window", "warmup", "fixpoint", "apply", "check-bookkeeping")
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
# The host's event for each program the runtime hands to a device (TPU
# v5 lite, jax 0.9). The profiler holds a fixed number of device events
# (6 * 2**20 there); a program dispatched well after the devices' record
# has ended shows that the record was cut short. (A program of transfers
# alone runs no op: one such comes a millisecond after the last op of a
# whole fixpoint.)
DISPATCH = "CommonPjRtLoadedExecutable::Execute"
CUT_AFTER_NS = 100e6


@dataclass(slots=True)
class Op:
    name: str
    start_ns: float
    dur_ns: float
    # shared by the ops of one HLO text: read, never changed
    stats: dict = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    devices: dict[int, list[Op]]            # device id -> ops, by start
    spans: list[tuple[str, float, float]]   # benchmark host spans
    dispatches: list[float] = field(default_factory=list)  # their starts

    def window_span(self) -> tuple[float, float]:
        """Start and end of the host span ``window``."""
        found = [(s, e) for name, s, e in self.spans if name == "window"]
        if len(found) != 1:
            raise ValueError(f"{len(found)} 'window' spans in the trace")
        return found[0]

    def cut_at(self) -> "float | None":
        """Where the devices' record ends, if the profiler cut it short:
        the end of the last op any device recorded, where the host
        dispatched a program more than ``CUT_AFTER_NS`` after it inside
        the ``window`` span; else None."""
        _, hi = self.window_span()
        last = max((o.end_ns for ops in self.devices.values() for o in ops),
                   default=None)
        if last is not None and any(last + CUT_AFTER_NS < t < hi
                                    for t in self.dispatches):
            return last
        return None

    def window(self) -> tuple[float, float]:
        """Start and end of the traced stretch of the window, on the
        trace's clock: the ``window`` span, ended where the devices'
        record ends if the profiler cut it short."""
        lo, hi = self.window_span()
        cut = self.cut_at()
        return (lo, hi) if cut is None else (lo, cut)

    def window_ops(self, device: int) -> list[Op]:
        lo, hi = self.window()
        return [o for o in self.devices.get(device, ())
                if o.end_ns > lo and o.start_ns < hi]


def _parse_name(name: str) -> tuple[str, dict]:
    """An op's short name and stats from its event's name. On a TPU the
    event's name is the op's HLO text (``%sort.3 = s32[8]{0}
    sort(...)``), which holds its operand and result shapes: the op
    keeps the name before `` = `` and the whole text as its
    ``long_name``."""
    head, sep, _ = name.partition(" = ")
    if not sep:
        return name, {}
    return head.lstrip("%"), {"long_name": name}


def read_xplane(path: Path) -> Trace:
    """Read the profiler's ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: dict[int, list[Op]] = {}
    spans: list[tuple[str, float, float]] = []
    dispatches: list[float] = []
    parsed: dict[str, tuple[str, dict]] = {}   # ops repeat by the million
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops = devices.setdefault(int(m.group(1)), [])
                for e in line.events:
                    name = e.name
                    short, stats = parsed.get(name) or parsed.setdefault(
                        name, _parse_name(name))
                    ops.append(Op(short, e.start_ns, e.duration_ns, stats))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name in SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == DISPATCH:
                        dispatches.append(e.start_ns)
    for ops in devices.values():
        ops.sort(key=lambda o: o.start_ns)
    return Trace(devices=devices, spans=sorted(spans, key=lambda s: s[1]),
                 dispatches=sorted(dispatches))


class Capture:
    """The profiler's recording of a stretch of the window. ``start``
    and ``stop`` bracket the stretch; ``read`` parses the recording once
    the window has closed, so that parsing takes no time from it, and
    removes it. A long recording on a TPU loses events once the
    profiler's buffers are full, so the stretch is kept short."""

    def __init__(self):
        self.logdir = tempfile.mkdtemp(prefix="chipbench-trace-")

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.logdir)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def read(self) -> Trace:
        try:
            files = sorted(Path(self.logdir).rglob("*.xplane.pb"))
            if len(files) != 1:
                raise RuntimeError(f"{len(files)} xplane files under "
                                   f"{self.logdir}")
            return read_xplane(files[0])
        finally:
            self.discard()

    def discard(self) -> None:
        shutil.rmtree(self.logdir, ignore_errors=True)


def span(name: str):
    """A benchmark host span in the profiler's trace (a no-op cost when
    no trace is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


# -- reductions -----------------------------------------------------------------

class NothingToRead(LookupError):
    """A reader found nothing to read in this run; the message says
    what was missing. The harness leaves the metric out of the result
    line and prints the message on standard error."""

def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals clipped to ``[lo, hi]``,
    as disjoint intervals in order."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, device: int) -> float:
    """Nanoseconds of the window in which some operation ran on the
    device."""
    lo, hi = trace.window()
    ops = trace.window_ops(device)
    return sum(e - s for s, e in union(((o.start_ns, o.end_ns) for o in ops),
                                       lo, hi))


def idle_gaps(trace: Trace, device: int) -> list[tuple[float, float]]:
    """The stretches of the window in which no operation ran on the
    device."""
    lo, hi = trace.window()
    busy = union(((o.start_ns, o.end_ns) for o in trace.window_ops(device)),
                 lo, hi)
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    return gaps


def host_span_at(trace: Trace, t: float) -> str:
    """The innermost benchmark span open at time ``t``."""
    best, width = "none", float("inf")
    for name, s, e in trace.spans:
        if s <= t < e and e - s < width:
            best, width = name, e - s
    return best


def op_kind(op: Op) -> str:
    """An op's name without XLA's suffixes (``sort.12`` -> ``sort``,
    ``broadcast_in_dim.96.clone`` -> ``broadcast_in_dim``)."""
    return op.name.split(".", 1)[0] or op.name


def breakdown(trace: Trace, device: int, top: int = 10) -> dict:
    """The device operations that took most time in the window, by kind,
    and the longest idle gaps, each labelled with the benchmark span
    the host was in at the gap's middle."""
    by_kind: dict[str, float] = {}
    for o in trace.window_ops(device):
        by_kind[op_kind(o)] = by_kind.get(op_kind(o), 0.0) + o.dur_ns
    ops = sorted(by_kind.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace, device), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, ns / 1e9] for k, ns in ops],
            "idle_gaps": [[host_span_at(trace, (s + e) / 2), (e - s) / 1e9]
                          for s, e in gaps]}


def _device_trace(trace: "Trace | None") -> "Trace":
    if trace is None:
        raise NothingToRead("the run took no trace")
    if not trace.devices:
        raise NothingToRead(f"the trace has no {OPS_LINE!r} line on a "
                            f"device plane")
    return trace


def idle_share(trace: "Trace | None", chips: int) -> float:
    """Percent of the window in which no operation ran, averaged over
    the chips used."""
    used = sorted(_device_trace(trace).devices)[:chips]
    lo, hi = trace.window()
    busy = sum(busy_ns(trace, d) for d in used) / len(used)
    return 100.0 * (1.0 - busy / (hi - lo))


def is_sort(op: Op) -> bool:
    return op_kind(op) == "sort"


def seconds_per_step(trace: "Trace | None", window, match) -> float:
    """Device seconds, on the first chip, of the ops ``match`` accepts,
    per traced step of the window."""
    trace = _device_trace(trace)
    cut = trace.cut_at()
    if cut is not None:
        lo, hi = trace.window_span()
        raise NothingToRead(
            f"the profiler's record ends {(cut - lo) / 1e9:.3f} s into the "
            f"{(hi - lo) / 1e9:.3f} s traced: its buffer of device events "
            f"was full, so the traced steps are not whole")
    ops = [o for o in trace.window_ops(min(trace.devices)) if match(o)]
    if not ops:
        raise NothingToRead(f"no op that {match.__name__} accepts ran on "
                            f"device {min(trace.devices)} in the window")
    return sum(o.dur_ns for o in ops) / 1e9 / window.traced_steps


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")


def shape_bytes(text: str) -> list[int]:
    """Bytes of each array shape written in HLO text, in order."""
    out = []
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append(n * _DTYPE_BYTES[dtype])
    return out


def custom_call_bytes(op: Op) -> tuple[list[int], list[int]] | None:
    """(result bytes, operand bytes) of a custom call, from the HLO text
    the trace keeps for it; None where the trace holds no such text."""
    text = str(op.stats.get("long_name", ""))
    head, sep, _ = text.partition(" custom-call(")
    ops = re.search(
        r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}", text)
    if not sep or ops is None:
        return None
    return shape_bytes(head.partition("=")[2]), shape_bytes(ops.group(1))


def roofline_share(trace: "Trace | None", kind: str, min_bytes,
                   device_kind: str):
    """Percent of the HBM roofline a kernel reached on the first chip:
    the least time its algorithmic bytes need at the chip's peak
    bandwidth, over the kernel's device time. ``min_bytes(results,
    operands)`` counts the bytes from the kernel's shapes."""
    from chipbench.peaks import peaks
    trace = _device_trace(trace)
    ops = [o for o in trace.window_ops(min(trace.devices))
           if op_kind(o) == kind]
    if not ops:
        raise NothingToRead(f"no op named {kind!r} ran on device "
                            f"{min(trace.devices)} in the window")
    sizes = [custom_call_bytes(o) for o in ops]
    if any(s is None for s in sizes):
        raise NothingToRead(f"the trace holds no custom-call HLO text "
                            f"(stat 'long_name') for {kind!r}")
    need = sum(min_bytes(res, opnds) for res, opnds in sizes)
    spent = sum(o.dur_ns for o in ops) / 1e9
    return 100.0 * need / peaks(device_kind)["hbm_bytes_per_s"] / spent
