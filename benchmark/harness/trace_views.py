"""Views of a traced window that several per-layer metrics share."""

from __future__ import annotations

from typing import List

from benchmark.harness import stats, xplane


def step_events(obs) -> List[xplane.Event]:
    """Executions of the busiest program inside the traced window (line
    ``XLA Modules`` of the first chip): in a training window that is the
    train step."""
    if obs.trace is None or not obs.trace.device_modules:
        return []
    lo, hi = obs.trace_window
    evs = [e for e in next(iter(obs.trace.device_modules.values()))
           if e.start >= lo and e.end <= hi]
    by_name = {}
    for e in evs:
        by_name.setdefault(e.name, []).append(e)
    if not by_name:
        return []
    return max(by_name.values(), key=lambda v: sum(e.dur for e in v))


def step_device_ms(obs):
    evs = step_events(obs)
    return stats.median([e.dur / 1e6 for e in evs]) if evs else None


def is_mosaic(event: xplane.Event) -> bool:
    """A Pallas (Mosaic) kernel among the device operations. The trace
    names an operation by its whole HLO line (``%name = shape
    opcode(operands)``): the kernel is the line whose own opcode is
    ``custom-call``; a fusion that merely takes ``%custom-call.10`` as an
    operand is not."""
    head = event.name.split(" = ", 1)[0]
    return "tpu_custom_call" in head or " custom-call(" in event.name


def mosaic_ms_per_step(obs):
    """Device time of the Mosaic kernels inside the traced steps, per
    step."""
    steps = step_events(obs)
    if not steps or not obs.trace.device_ops:
        return None
    lo, hi = min(e.start for e in steps), max(e.end for e in steps)
    ops = next(iter(obs.trace.device_ops.values()))
    total = sum(e.dur for e in ops
                if is_mosaic(e) and e.start >= lo and e.end <= hi)
    if total == 0:
        return None
    return total / 1e6 / len(steps)


def decode_span_device_ms(obs) -> List[float]:
    """Device-busy time inside each ``adapter.decode`` span of the traced
    window."""
    if obs.trace is None or not obs.trace.device_ops:
        return []
    return [xplane.device_seconds_in(obs.trace, (e.start, e.end)) * 1e3
            for e in xplane.spans_named(obs.trace, "adapter.decode",
                                        obs.trace_window)]
