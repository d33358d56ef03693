"""From a profiler trace (``.xplane.pb``) to numbers: device busy time,
time by operation name, collective time exposed, idle gaps by what the
host was doing. Read with ``jax.profiler.ProfileData`` and nothing else.

The arithmetic works on plain ``(name, start_ns, duration_ns)`` tuples so
that it can be checked by hand on a small fixture
(``tests/perfbench/test_trace_reduce.py``); only :func:`load` touches the
profiler's file format.
"""

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
# names of collective operations as XLA's TPU backend spells them
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)
# host annotations the benchmark itself writes, and the program's brackets
ANNOTATION = re.compile(r"^(perfbench|ds)\.")
WINDOW_ANNOTATION = "perfbench.window"


@dataclasses.dataclass
class Trace:
    """Events of one traced run. ``device_ops[i]`` are the operations of
    chip ``i``; ``host`` are the benchmark's and the program's annotations
    (``perfbench.*``, ``ds.*``), every host thread together. All on the
    profiler's clock, in ns."""
    device_ops: Dict[int, List[Event]]
    host: List[Event]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


# a CPU run has no device plane; its XLA thunks run on host threads of this
# name. Read only when a test rehearses the traced path on the CPU.
CPU_REHEARSAL_LINE = re.compile(r"^tf_XLAPjRtCpuClient")


def load(path: str, cpu_rehearsal: bool = False) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if cpu_rehearsal and plane.name == "/host:CPU":
            device_ops[0] = [
                (e.name, int(e.start_ns), int(e.duration_ns))
                for line in plane.lines
                if CPU_REHEARSAL_LINE.match(line.name) for e in line.events]
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[int(m.group(1))] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if ANNOTATION.match(e.name))
    return Trace(device_ops, host)


# ---------------------------------------------------------------- arithmetic
def clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    """The parts of ``events`` inside ``[lo, hi)``."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals; sorted, disjoint."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _spans(events: Sequence[Event]) -> List[Tuple[int, int]]:
    return [(s, s + d) for _, s, d in events]


def busy_ns(events: Sequence[Event]) -> int:
    """Time in which at least one of ``events`` ran."""
    return sum(e - s for s, e in union(_spans(events)))


def subtract(a: Sequence[Tuple[int, int]],
             b: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The parts of the disjoint sorted intervals ``a`` that no interval
    of the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# A device event's name is the whole HLO instruction, "%name = type
# opcode(operands), attributes". The opcode is the first lower-case word
# followed by "(" (types and layouts spell theirs in capitals).
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
# operations that only contain others: their time is their bodies'
CONTAINERS = frozenset({"while", "conditional", "call"})


def short_name(name: str) -> Tuple[str, str]:
    """``(instruction name, opcode)`` of an event name; a name that is no
    HLO instruction is returned whole with an empty opcode."""
    m = _INSTRUCTION.match(name)
    return (m.group(1), m.group(2)) if m else (name, "")


_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")


def operand_bytes(name: str) -> int:
    """Bytes of the operands of the HLO instruction ``name`` (the arithmetic
    of ``deepspeed_tpu/utils/hlo_inspect.parse_collectives``, read from the
    event's own text: what one device feeds the operation). An asynchronous
    pair is counted at its ``-start``; a ``-done`` gives 0."""
    _, op = short_name(name)
    if not op or op.endswith("-done"):
        return 0
    start = name.index(f" {op}(") + len(op) + 1
    depth, end = 0, len(name)
    for i in range(start, len(name)):
        depth += (name[i] == "(") - (name[i] == ")")
        if depth == 0:
            end = i
            break
    total = 0
    for dtype, dims in _SHAPE.findall(name[start:end]):
        bits = 8 if dtype == "pred" else int(re.search(r"\d+$", dtype).group())
        count = 1
        for d in dims.split(","):
            count *= int(d) if d else 1
        total += (count * bits + 7) // 8
    return total


def leaves(events: Sequence[Event]) -> List[Event]:
    """``events`` without the operations that only contain others."""
    return [e for e in events if short_name(e[0])[1] not in CONTAINERS]


def time_by_name(events: Sequence[Event]) -> Dict[str, int]:
    """Time by short name (``attn.23 custom-call``), containers left out."""
    out: Dict[str, int] = {}
    for name, _, dur in leaves(events):
        key = " ".join(x for x in short_name(name) if x)
        out[key] = out.get(key, 0) + dur
    return out


def time_matching(events: Sequence[Event], pattern: str) -> Tuple[int, int]:
    """``(total ns, count)`` of the leaf events whose full name matches the
    regular expression ``pattern`` anywhere."""
    rx = re.compile(pattern)
    hits = [d for n, _, d in leaves(events) if rx.search(n)]
    return sum(hits), len(hits)


def exposed_collective_ns(events: Sequence[Event]) -> int:
    """Time in which a collective ran on this device and no other
    operation did: collective time that compute does not hide."""
    events = leaves(events)
    coll = union(_spans([e for e in events if COLLECTIVE.search(e[0])]))
    rest = union(_spans([e for e in events if not COLLECTIVE.search(e[0])]))
    return sum(e - s for s, e in subtract(coll, rest))


def idle_gaps(events: Sequence[Event], lo: int, hi: int,
              host: Sequence[Event]) -> Dict[str, int]:
    """Idle time of one device inside ``[lo, hi)``, by the annotation
    that covers the middle of each gap (the innermost, that is
    the shortest, where several do; the window's own annotation only when
    nothing else does) and ``unattributed`` where none does."""
    gaps = subtract([(lo, hi)], union(_spans(events)))
    # one sweep over the gaps and the annotations, both by time: a serving
    # trace holds some 1e5 gaps under some 1e4 program brackets
    by_start = sorted((hs, hs + d, d, n) for n, hs, d in host
                      if n != WINDOW_ANNOTATION)
    out: Dict[str, int] = {}
    open_now, nxt = [], 0
    for s, e in gaps:
        mid = (s + e) // 2
        while nxt < len(by_start) and by_start[nxt][0] <= mid:
            open_now.append(by_start[nxt])
            nxt += 1
        open_now = [a for a in open_now if a[1] > mid]
        name = (min((d, n) for _, _, d, n in open_now)[1] if open_now
                else "unattributed")
        out[name] = out.get(name, 0) + (e - s)
    return out


def window_of(trace: Trace) -> Tuple[int, int]:
    """``[lo, hi)`` of the measured window: the benchmark's
    ``perfbench.window`` annotation, or, without one, first to last device
    event."""
    for name, start, dur in trace.host:
        if name == WINDOW_ANNOTATION:
            return start, start + dur
    every = [e for ops in trace.device_ops.values() for e in ops]
    if not every:
        raise ValueError("the trace holds no device operation")
    return (min(s for _, s, _ in every), max(s + d for _, s, d in every))


def window_events(trace: Trace) -> Dict[int, List[Event]]:
    """Each chip's device operations, clipped to the measured window."""
    lo, hi = window_of(trace)
    return {chip: clip(ops, lo, hi) for chip, ops in trace.device_ops.items()}


def summarize(trace: Trace, top: int = 10) -> dict:
    """What the result line and the readers need, in seconds:

    - ``window_s``; ``busy_s`` averaged over the chips in the trace;
    - ``op_seconds``: device time by operation name, averaged over chips;
    - ``exposed_collective_s``, averaged over chips;
    - ``idle_gap_seconds``: idle time by host annotation, averaged;
    - ``breakdown``: the ``top`` of both, as lists for the result line.
    """
    lo, hi = window_of(trace)
    chips = sorted(trace.device_ops)
    if not chips:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    busy = exposed = 0
    ops: Dict[str, int] = {}
    gaps: Dict[str, int] = {}
    for chip in chips:
        events = clip(trace.device_ops[chip], lo, hi)
        busy += busy_ns(events)
        exposed += exposed_collective_ns(events)
        for k, v in time_by_name(events).items():
            ops[k] = ops.get(k, 0) + v
        for k, v in idle_gaps(events, lo, hi, trace.host).items():
            gaps[k] = gaps.get(k, 0) + v
    n = len(chips) * 1e9
    op_seconds = {k: v / n for k, v in ops.items()}
    gap_seconds = {k: v / n for k, v in gaps.items()}

    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n,
            "chips": len(chips), "op_seconds": op_seconds,
            "exposed_collective_s": exposed / n,
            "idle_gap_seconds": gap_seconds,
            "breakdown": {"device_ops": ranked(op_seconds),
                          "idle_gaps": ranked(gap_seconds)}}
