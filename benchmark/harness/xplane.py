"""From a profiler trace (.xplane.pb) to device busy time, per-operation
time, per-span device time and labelled idle gaps.

Read with ``jax.profiler.ProfileData`` (pure parsing: no backend is
initialised). What a TPU trace holds, as seen on the v5e: one plane per
chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per
executed HLO operation (name = HLO instruction, e.g. ``fusion.12``) and
whose line ``XLA Modules`` has one event per executed program; the host
plane ``/host:CPU`` has one line per thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names. All
times are nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]            # [start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclass
class Event:
    name: str
    start: int
    dur: int
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Trace:
    device_ops: Dict[str, List[Event]]        # plane name -> XLA Ops
    device_modules: Dict[str, List[Event]]    # plane name -> XLA Modules
    host_spans: List[Event]                   # every host-plane event
    lines_seen: Dict[str, List[str]]          # plane -> line names


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_names: Optional[set] = None,
         keep_stats: bool = True) -> Trace:
    """``host_names``: keep only host events with these names (a host
    plane holds very many)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, host, seen = {}, {}, [], {}
    for plane in data.planes:
        pname = plane.name
        is_dev = pname.startswith("/device:") and "TPU" in pname \
            and "Core" not in pname.split(":")[-1]
        for line in plane.lines:
            seen.setdefault(pname, []).append(line.name)
            if is_dev and line.name in (OPS_LINE, MODULES_LINE):
                evs = []
                for e in line.events:
                    st = {}
                    if keep_stats:
                        try:
                            st = {k: v for k, v in e.stats}
                        except Exception:  # noqa: BLE001
                            st = {}
                    evs.append(Event(e.name, int(e.start_ns),
                                     int(e.duration_ns), st))
                (ops if line.name == OPS_LINE else modules).setdefault(
                    pname, []).extend(evs)
            elif pname.startswith("/host:"):
                for e in line.events:
                    if host_names is None or e.name in host_names:
                        host.append(Event(e.name, int(e.start_ns),
                                          int(e.duration_ns)))
    return Trace(ops, modules, host, seen)


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def window_of(trace: Trace) -> Interval:
    """The traced window: the benchmark's own ``bench.window`` span if
    the host plane holds it, else first to last device operation."""
    spans = [e for e in trace.host_spans if e.name == WINDOW_SPAN]
    if spans:
        s = max(spans, key=lambda e: e.dur)
        return (s.start, s.end)
    evs = [e for lst in trace.device_ops.values() for e in lst]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return (min(e.start for e in evs), max(e.end for e in evs))


def busy_intervals(events: List[Event]) -> List[Interval]:
    return merge([(e.start, e.end) for e in events])


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.device_ops:
        return 0.0
    per_chip = [total(clip(busy_intervals(evs), *window))
                for evs in trace.device_ops.values()]
    return sum(per_chip) / len(per_chip) / 1e9


def short_name(hlo_line: str, width: int = 96) -> str:
    """``%fusion.8 = bf16[16,1023,50257]{...} fusion(...)`` ->
    ``%fusion bf16[16,1023,50257] fusion``: the instruction without its
    number, the shape it makes (where it makes one array) and its
    opcode."""
    if " = " not in hlo_line:
        return hlo_line[:width]
    head, rhs = hlo_line.split(" = ", 1)
    shape = "" if rhs.startswith("(") else rhs.split("{", 1)[0].strip()
    opcode = re.search(r"\s([a-z][\w\-]*)\(", " " + rhs)
    # %fusion.3607 and %fusion.3608 are the same work in two layers:
    # without the number they add up under one name
    head = re.sub(r"\.\d+$", "", head)
    return " ".join(x for x in (head, shape, opcode.group(1) if opcode
                                else "") if x)[:width]


def top_ops(trace: Trace, window: Interval, n: int = 10
            ) -> List[Tuple[str, float]]:
    """Device operations by total time in the window, over all chips."""
    acc: Dict[str, int] = {}
    for evs in trace.device_ops.values():
        for e in evs:
            if e.end > window[0] and e.start < window[1]:
                key = short_name(e.name)
                acc[key] = acc.get(key, 0) + e.dur
    nchips = max(1, len(trace.device_ops))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / nchips / 1e9) for k, v in ranked]


def device_seconds_in(trace: Trace, span: Interval) -> float:
    """Device-busy seconds inside one host span (first chip)."""
    if not trace.device_ops:
        return 0.0
    evs = next(iter(trace.device_ops.values()))
    return total(clip(busy_intervals(evs), *span)) / 1e9


def spans_named(trace: Trace, name: str, window: Interval) -> List[Event]:
    return sorted((e for e in trace.host_spans
                   if e.name == name and e.start >= window[0]
                   and e.end <= window[1]), key=lambda e: e.start)


def idle_gaps(trace: Trace, window: Interval, labels: List[str],
              other: str = "between-steps", n: int = 10
              ) -> List[Tuple[str, float]]:
    """The first chip's idle gaps inside the window, each given to the
    benchmark span (by ``labels``, innermost last) that covers its
    middle, summed by label and ranked."""
    if not trace.device_ops:
        return []
    evs = next(iter(trace.device_ops.values()))
    busy = clip(busy_intervals(evs), *window)
    gaps, cursor = [], window[0]
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < window[1]:
        gaps.append((cursor, window[1]))
    spans = [e for e in trace.host_spans if e.name in labels]
    acc: Dict[str, int] = {}
    for s, e in gaps:
        mid = (s + e) // 2
        label = other
        for name in labels:           # later labels are innermost
            if any(sp.start <= mid < sp.end for sp in spans
                   if sp.name == name):
                label = name
        acc[label] = acc.get(label, 0) + (e - s)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in ranked]


def describe(trace: Trace, window: Interval, n: int = 40) -> str:
    """For a look by hand: planes, lines and the busiest operations."""
    out = []
    for plane, lines in trace.lines_seen.items():
        out.append(f"plane {plane}: lines {sorted(set(lines))[:12]}")
    out.append(f"window {window} = {(window[1] - window[0]) / 1e9:.3f}s, "
               f"busy {busy_seconds(trace, window):.3f}s")
    for name, sec in top_ops(trace, window, n):
        out.append(f"  op {sec * 1e3:10.3f} ms  {name}")
    for plane, evs in trace.device_modules.items():
        names: Dict[str, List[int]] = {}
        for e in evs:
            names.setdefault(e.name, []).append(e.dur)
        for k, v in sorted(names.items(), key=lambda kv: -sum(kv[1]))[:12]:
            out.append(f"  module {plane} {k}: n={len(v)} "
                       f"total {sum(v) / 1e6:.3f} ms")
    for evs in trace.device_ops.values():
        for e in evs[:3]:
            out.append(f"  sample op {e.name}: stats {e.stats}")
        break
    return "\n".join(out)
