"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData``: per device, the program executions of
the "XLA Modules" line and the operations of the "XLA Ops" line, and
the host's main thread (its line is named after the process, such as
"python3", or "main/<tid>"), whose program spans and
``PjitFunction(...)``, dispatch and transfer spans say what the host
was doing. ``reduce`` is a pure function of those events. On a TPU an operation's event name is its HLO text, so a
kernel's call shows as ``%<name> = <shapes> custom-call(...)``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

# container operations whose event spans the operations inside them
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    programs: Dict[int, List[Event]]     # device id -> program executions
    ops: Dict[int, List[Event]]          # device id -> operations
    host: List[Event]                    # host spans of the main thread
    marks: Dict[str, Tuple[float, float]] = dataclasses.field(
        default_factory=dict)            # "bench:*" annotations (start, end)


def program_name(name: str) -> str:
    """``jit_train_layer_chapter(1120...)`` -> ``train_layer_chapter``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def op_parts(name: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an HLO-text event name."""
    inst, sep, rest = name.partition(" = ")
    if not sep:
        return name, ""
    rest = rest.lstrip()
    if rest.startswith("("):          # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return inst.lstrip("%"), rest.lstrip().partition("(")[0]


def kernel_of(name: str) -> Optional[str]:
    """The ``ff_dense`` kernel an operation event is a call of, if any:
    "ff_dense_bwd" for the fused backward, "ff_dense" for the forward."""
    inst, opcode = op_parts(name)
    if opcode != "custom-call" or "ff_dense" not in inst:
        return None
    return "ff_dense_bwd" if "ff_dense_bwd" in inst else "ff_dense"


def union_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    total, end = 0.0, lo
    for e in sorted(events, key=lambda e: e.start_ns):
        s, t = max(e.start_ns, end), min(e.end_ns, hi)
        if t > s:
            total += t - s
        end = max(end, min(e.end_ns, hi))
    return total


def gaps_ns(events: Sequence[Event], lo: float, hi: float):
    """(start, end) of every interval inside [lo, hi] that no event
    covers."""
    out, end = [], lo
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.start_ns > end:
            out.append((end, min(e.start_ns, hi)))
        end = max(end, e.end_ns)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(s, t) for s, t in out if t > s]


def host_label(host: Sequence[Event], t: float) -> str:
    """The innermost host span open at time t."""
    open_ = [e for e in host if e.start_ns <= t < e.end_ns]
    if not open_:
        return "host: in Python, outside any traced call"
    return "host: " + min(open_, key=lambda e: e.dur_ns).name


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: Dict[int, float]                       # per device
    by_program: Dict[str, Tuple[float, int]]       # seconds, executions
    by_kernel: Dict[str, Tuple[float, int]]        # seconds, calls
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def reduce(trace: Trace, window: Tuple[float, float], top: int = 10
           ) -> Reduced:
    """Busy and idle time per device, device time by program and by
    kernel, the operations that took most time and the longest idle
    gaps labelled by what the host was doing, inside ``window`` (ns)."""
    lo, hi = window
    busy = {d: union_ns(ev, lo, hi) / 1e9 for d, ev in trace.programs.items()}
    by_program = collections.defaultdict(lambda: [0.0, 0])
    for ev in trace.programs.values():
        for e in ev:
            if lo <= e.start_ns < hi:
                slot = by_program[program_name(e.name)]
                slot[0] += e.dur_ns / 1e9
                slot[1] += 1
    by_kernel = collections.defaultdict(lambda: [0.0, 0])
    by_op = collections.Counter()
    for ev in trace.ops.values():
        for e in ev:
            if not lo <= e.start_ns < hi:
                continue
            inst, opcode = op_parts(e.name)
            if opcode in _CONTAINERS:
                continue
            by_op[re.sub(r"\.\d+$", "", inst)] += e.dur_ns / 1e9
            k = kernel_of(e.name)
            if k is not None:
                by_kernel[k][0] += e.dur_ns / 1e9
                by_kernel[k][1] += 1
    gaps = sorted(((t - s, s, d) for d, ev in trace.programs.items()
                   for s, t in gaps_ns(ev, lo, hi)), reverse=True)[:top]
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=busy,
        by_program={k: (v[0], v[1]) for k, v in by_program.items()},
        by_kernel={k: (v[0], v[1]) for k, v in by_kernel.items()},
        top_ops=[(k, v) for k, v in by_op.most_common(top)],
        idle_gaps=[(f"device {d} idle, "
                    + host_label(trace.host, s + dt / 2), dt / 1e9)
                   for dt, s, d in gaps])


def load(log_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    programs, ops, host, marks = {}, {}, [], {}
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Modules", "XLA Ops"):
                dest = programs if line.name == "XLA Modules" else ops
                dest.setdefault(int(m.group(1)), []).extend(
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events)
            elif plane.name == "/host:CPU":
                for e in line.events:
                    if e.name.startswith("bench:"):
                        marks[e.name] = (e.start_ns, e.start_ns
                                         + e.duration_ns)
                    elif line.name.startswith(("python", "main/")):
                        host.append(Event(e.name, e.start_ns,
                                          e.duration_ns))
    return Trace(programs, ops, host, marks)
