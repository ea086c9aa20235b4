"""Reduction of a profiler trace to device busy time, kernel time and gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Device planes are named
``/device:<KIND>:<n>``, ``n`` the device's id; on each, the line of XLA
operations holds one event per operation that ran, with a start and a
duration in nanoseconds. Only the planes of the cell's own chips are kept.
Host planes hold the harness's ``TraceAnnotation`` spans (names starting
with ``bench.``) and the program's (``engine.``), on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

OPS_LINE = re.compile(r"XLA Ops")
DEVICE_PLANE = re.compile(r"^/device:[^:]+:(\d+)")
HOST_PREFIX = ("bench.", "engine.")
WINDOW_SPAN = "bench.traced"    # the traced window itself: names no gap
HOLDER = re.compile(r"\s(while|conditional|call|[\w-]+-start)\(")
# An op event's name is its HLO instruction, e.g.
#   %checkpoint.69 = f32[1024,9728]{1,0:T(8,128)} custom-call(bf16[1024,2560]
#   {...} %bitcast.217, s8[2560,9728]{...} %fusion.33, ...),
#   custom_call_target="tpu_custom_call", ...
# A Pallas kernel's own name is not in it, so kernels are told apart by the
# types of their results and operands (printed inline, or else only in the
# call's operand_layout_constraints).
CUSTOM_CALL = re.compile(r"= (.*?) custom-call\((.*?)\), "
                         r'custom_call_target="tpu_custom_call"')
OPERAND_LAYOUTS = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")
TYPE = re.compile(r"\b(pred|bf16|f8e\w+|[fsu]\d+)\[([\d,]*)\]")
# A collective's HLO opcode, synchronous or as an asynchronous pair, as
# its instruction calls it (``all-reduce(``, ``all-gather-start(``) or as
# the op's own name begins (``%all-reduce.3``, ``all-gather-done.1``).
COLLECTIVES = r"(?:all-reduce|all-gather|reduce-scatter|collective-permute" \
    r"|all-to-all)(?:-start|-done)?"
COLLECTIVE_CALL = re.compile(r"\s" + COLLECTIVES + r"\(")
COLLECTIVE_NAME = re.compile(r"^%?" + COLLECTIVES + r"(?:[.\-]|$)")
Type = Tuple[str, Tuple[int, ...]]


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    text: str = ""      # the name and the event's string statistics

    def __post_init__(self):
        self.text = self.text or self.name


def _text(e) -> str:
    """An op event's name with its string statistics (HLO op, long name),
    where a kernel's own name may sit when the op name is generic."""
    st = e.stats
    pairs = st.items() if hasattr(st, "items") else st
    vals = [v for _, v in pairs if isinstance(v, str)]
    return " ".join([e.name] + vals)


@dataclasses.dataclass
class Reduced:
    """What the readers need from one trace."""
    ops: Dict[str, List[Event]]           # the cell's device plane -> ops
    host: List[Event]                      # the harness's and engine's spans
    window: Interval                       # host clock, ns


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def load(path: str, window: Interval, devices: Sequence[int]) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, window, devices)


def device_id(plane: str) -> Optional[int]:
    """The device id of a device plane's name, or None."""
    m = DEVICE_PLANE.match(plane)
    return int(m.group(1)) if m else None


def reduce_planes(planes, window: Interval,
                  devices: Sequence[int]) -> Reduced:
    """The op events of the device planes of ``devices`` and the host
    spans, from ``ProfileData`` planes."""
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            if device_id(plane.name) not in devices:
                continue
            evs = []
            for line in plane.lines:
                if OPS_LINE.search(line.name):
                    evs.extend(Event(e.name, e.start_ns, e.end_ns, _text(e))
                               for e in line.events)
            if evs:
                ops[plane.name] = sorted(evs, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return Reduced(ops=ops, host=sorted(host, key=lambda e: e.start),
                   window=window)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(events: Sequence[Event], window: Interval) -> float:
    """Length of the union of the events' intervals inside ``window``."""
    return sum(e - s for s, e in clip(union([(ev.start, ev.end)
                                             for ev in events]), window))


def idle_gaps(events: Sequence[Event], window: Interval) -> List[Interval]:
    """The stretches of ``window`` in which no event ran."""
    gaps, cur = [], window[0]
    for s, e in clip(union([(ev.start, ev.end) for ev in events]), window):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < window[1]:
        gaps.append((cur, window[1]))
    return gaps


def _types(text: str) -> List[Type]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in TYPE.findall(text)]


def custom_call_types(text: str
                      ) -> Optional[Tuple[List[Type], List[Type]]]:
    """(result types, operand types) of a Mosaic kernel's HLO instruction,
    or None where ``text`` is no such instruction."""
    m = CUSTOM_CALL.search(text)
    if m is None:
        return None
    ins = _types(m.group(2))
    if not ins:
        lay = OPERAND_LAYOUTS.search(text)
        ins = _types(lay.group(1)) if lay else []
    return _types(m.group(1)), ins


def kernel_ns(events: Sequence[Event], match: Callable[[str], bool],
              window: Interval) -> float:
    """Summed device time of the events whose text (name and string
    statistics) ``match`` accepts."""
    return sum(e - s for ev in events if match(ev.text)
               for s, e in clip([(ev.start, ev.end)], window))


def is_collective(text: str) -> bool:
    """Whether an op's text is a collective between chips: all-reduce,
    all-gather, reduce-scatter, collective-permute or all-to-all, alone or
    the start or done of an asynchronous one."""
    return bool(COLLECTIVE_NAME.match(text)
                or COLLECTIVE_CALL.search(text.partition("), ")[0]))


def _fmt(t: Type) -> str:
    return f"{t[0]}[{','.join(map(str, t[1]))}]"


def short_name(text: str) -> str:
    """An op's HLO instruction without layouts, operand names or
    attributes: ``%x = f32[8,4] custom-call(bf16[8,2], s8[2,4])``."""
    name, eq, rest = text.partition(" = ")
    sig = custom_call_types(text)
    if sig is not None:
        out, ins = sig
        return (f"{name} = {', '.join(map(_fmt, out))} custom-call("
                f"{', '.join(map(_fmt, ins))})")
    for _ in range(2):
        rest = re.sub(r"\{[^{}]*\}", "", rest)
    rest = re.sub(r" ?%[\w.-]+", "", rest.split("), ", 1)[0])
    return name + eq + rest + (")" if "(" in rest else "")


def label(gap: Interval, host: Sequence[Event]) -> str:
    """The innermost driver span, other than the traced window's own, that
    covers the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for ev in host:
        if ev.name != WINDOW_SPAN and ev.start <= mid <= ev.end and (
                best is None or ev.end - ev.start < best.end - best.start):
            best = ev
    return best.name if best is not None else "inside generate"


def top_ops(events: Sequence[Event], window: Interval,
            n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` operations (by ``short_name``) with the most device
    seconds, leaving out those that only hold others (a loop, a call, an
    asynchronous copy's start), which would count their time twice."""
    tot: Dict[str, float] = {}
    for ev in events:
        if HOLDER.search(ev.name):
            continue
        for s, e in clip([(ev.start, ev.end)], window):
            k = short_name(ev.name)
            tot[k] = tot.get(k, 0.0) + (e - s) / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def top_gaps(events: Sequence[Event], host: Sequence[Event],
             window: Interval, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps, each named by what the host was doing."""
    gaps = sorted(idle_gaps(events, window), key=lambda g: g[0] - g[1])[:n]
    return [(label(g, host), (g[1] - g[0]) / 1e9) for g in gaps]
