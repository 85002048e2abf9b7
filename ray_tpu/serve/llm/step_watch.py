"""What an engine step that ran for ``RTPU_TRACE_SLOW_S`` (1 s) or more
waited for: the record ``LLMEngine.slow_steps()`` keeps of it
(docs/TRACING.md, "A slow step").

The step's span tree says where the time lay; it cannot say why a leaf
took a second. ``StepWatch`` adds what the engine thread cannot see of
itself while it waits: a thread that needs nothing but the interpreter
notes how late it wakes (the time in which NO Python thread of the
process could run) and, once the open step is a slow one, takes every
thread's stack. ``verdict`` then names the cause from the tree, the
step's CPU time, the lateness, the stacks and the process's ``py.gc`` and
``jax.trace`` / ``jax.lower`` / ``jax.compile`` events.
"""

from __future__ import annotations

import linecache
import logging
import re
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

from ray_tpu._private import tracing

logger = logging.getLogger(__name__)

RING = 64
FRAMES = 12
VERDICTS = ("compile", "gc", "interpreter held", "host starved", "lock",
            "device or runtime", "engine")
JAX_EVENTS, EVENTS = tracing.JAX_EVENTS, tracing.PROCESS_EVENTS

# a thread whose innermost Python frame stands at such a call waits for
# something else than the interpreter (the last two: this repo's RPC
# core, native and with a timeout, and asyncio's wake-up socket;
# ``self._read(`` / ``self._recv(``: execnet's reader thread, which every
# pytest-xdist worker has, stands in such a wrapper of a socket's read)
_WAIT_CALL = re.compile(
    r"\b(wait\w*|acquire|sleep|select|poll|_?recv\w*|accept|join|get"
    r"|_?read\w*|block_until_ready|result|asarray|\w*next_batch|send\w*)\(")
_WAIT_FUNCS = frozenset({"wait", "acquire", "select", "poll", "join",
                         "_wait_for_tstate_lock"})


def _walk(span: Dict[str, Any]) -> Iterator[Dict[str, Any]]:
    yield span
    for child in span["children"]:
        yield from _walk(child)


def _frames(frame) -> List[Tuple[str, int, str]]:
    out = []
    while frame is not None and len(out) < FRAMES:
        code = frame.f_code
        out.append((code.co_filename, frame.f_lineno, code.co_name))
        frame = frame.f_back
    return out


def _waits(frames: List[Tuple[str, int, str]]) -> bool:
    """The thread stood in a wait when its stack was taken, as far as its
    innermost Python frame shows (a C call has no frame of its own)."""
    if not frames:
        return True
    path, line, func = frames[0]
    return func in _WAIT_FUNCS or bool(
        _WAIT_CALL.search(linecache.getline(path, line)))


class StepWatch:
    """One an engine: ``begin`` / ``end`` round every step (the engine
    thread), and the thread ``rtpu-llm-watch`` between ``start`` and
    ``stop``. ``adapter_file``: the source file of the adapter's class,
    by which the engine thread's stack is known to be inside the model
    step."""

    def __init__(self, adapter_file: str):
        self.adapter_file = adapter_file
        self.records: deque = deque(maxlen=RING)
        self.total = 0                      # counted with tracing off too
        self._open: Optional[Dict[str, Any]] = None
        self._engine_ident: Optional[int] = None
        self._due = float("inf")    # when the watcher asked to wake next
        self._cpu = 0.0             # the process's CPU time at its last wake
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="rtpu-llm-watch")

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5.0)

    # ------------------------------------------------ the watcher thread

    def _loop(self):
        self._cpu = time.process_time()
        self._due = time.time() + tracing.slow_s() / 4
        while not self._stop.wait(max(0.0, self._due - time.time())):
            now = time.time()
            late_ms = (now - self._due) * 1e3
            was, self._cpu = self._cpu, time.process_time()
            due = now + tracing.slow_s() / 4
            st = self._open
            if st is not None:
                if late_ms > st["late_ms"]:
                    # the process's CPU time since the wake before: a held
                    # interpreter computed, a starved process did not
                    st["late_ms"] = late_ms
                    st["late_cpu_ms"] = (self._cpu - was) * 1e3
                if st["stacks"] is None:
                    slow_at = st["t0"] + tracing.slow_s()
                    if now >= slow_at:
                        st["stacks_at"], st["stacks"] = now, self._stacks()
                    else:           # be there when it turns slow
                        due = min(due, slow_at)
            self._due = due
        self._due = float("inf")

    def _stacks(self) -> Dict[str, List[Tuple[str, int, str]]]:
        names = {t.ident: t.name for t in threading.enumerate()}
        frames = sys._current_frames()
        out = {}
        # this engine's thread first: it keeps its plain name where
        # another engine of the process has a thread of the same name
        for ident in sorted(frames, key=lambda i: i != self._engine_ident):
            name = names.get(ident, f"thread-{ident}")
            if name in out:
                name = f"{name}-{ident}"
            out[name] = _frames(frames[ident])
        return out

    # -------------------------------------------------- the engine thread

    def begin(self, i: int, t0: float):
        if self._engine_ident is None:
            self._engine_ident = threading.get_ident()
        self._open = {"i": i, "t0": t0, "late_ms": 0.0, "late_cpu_ms": 0.0,
                      "stacks": None, "stacks_at": None}

    def end(self, t1: float, cpu_ms: float,
            tree: Optional[Dict[str, Any]]):
        st, self._open = self._open, None
        if st is None:
            return
        overdue_ms = (t1 - self._due) * 1e3
        if overdue_ms > st["late_ms"]:
            # the watcher is due and has not run yet: this thread got the
            # interpreter before it
            st["late_ms"] = overdue_ms
            st["late_cpu_ms"] = (time.process_time() - self._cpu) * 1e3
        if tree is not None and st["late_ms"] >= 1.0:
            # the watcher woke inside this step (or should have), late
            tree["attrs"]["watch_late_ms"] = st["late_ms"]
        if t1 - st["t0"] < tracing.slow_s():
            return
        self.total += 1
        if tree is None:            # RTPU_TRACING=0: counted, not kept
            return
        if st["stacks"] is None:
            # the watcher never got to it: the other threads' stacks as
            # they stand now (this thread's own says nothing any more)
            st["stacks_at"], st["stacks"] = t1, self._stacks()
            st["stacks"].pop(threading.current_thread().name, None)
        rec = {"i": st["i"], "t0": st["t0"], "t1": t1,
               "cpu_ms": cpu_ms, "tree": tree,
               "stacks": {
                   name: [f"{path}:{line} {func}"
                          for path, line, func in frames]
                   for name, frames in st["stacks"].items()},
               "stacks_at": st["stacks_at"],
               "engine_thread": threading.current_thread().name,
               "watch_late_ms": st["late_ms"],
               "watch_late_cpu_ms": st["late_cpu_ms"],
               "events": [e for e in tracing.step_roots(*EVENTS)
                          if e["t1"] > st["t0"] and e["t0"] < t1]}
        rec["verdict"], rec["why"] = verdict(
            rec, st["stacks"], self.adapter_file)
        self.records.append(rec)
        logger.warning(
            "llm.step %d took %.3f s (cpu %.1f ms): %s: %s", st["i"],
            t1 - st["t0"], cpu_ms, rec["verdict"], rec["why"])


def lock_wait_ms(tree: Dict[str, Any]) -> float:
    return sum(s["attrs"].get("lock_wait_ms", 0.0) for s in _walk(tree))


def _longest_leaf(tree: Dict[str, Any]) -> Tuple[float, str]:
    """(ms, name) of the leaf that took the most time, a span's own
    statements (what its child spans leave of it) counted as a leaf of
    its name."""
    best = (0.0, tree["name"])
    for s in _walk(tree):
        if s["name"] in EVENTS:
            continue
        own = (s["t1"] - s["t0"]) - sum(
            c["t1"] - c["t0"] for c in s["children"]
            if c["name"] not in EVENTS)
        best = max(best, (own * 1e3, s["name"]))
    return best


def verdict(rec: Dict[str, Any],
            stacks: Dict[str, List[Tuple[str, int, str]]],
            adapter_file: str) -> Tuple[str, str]:
    """(one of ``VERDICTS``, a sentence with the numbers behind it).
    "Most of it" is more than half of the step."""
    t0, t1, tree = rec["t0"], rec["t1"], rec["tree"]
    dur_ms = (t1 - t0) * 1e3
    half = dur_ms / 2
    inside = {name: 0.0 for name in EVENTS}
    for e in [s for s in _walk(tree) if s["name"] in EVENTS] + rec["events"]:
        inside[e["name"]] += (min(e["t1"], t1) - max(e["t0"], t0)) * 1e3
    # a program's first call: traced, lowered, then compiled or read
    # from the cache
    compiles = sum(inside[name] for name in JAX_EVENTS)
    if compiles > half:
        where = next((s["name"] for s in _walk(tree) if any(
            c["name"] in JAX_EVENTS for c in s["children"])), None)
        return "compile", (
            f"{compiles:.0f} ms of compiles (trace "
            f"{inside['jax.trace']:.0f}, lower {inside['jax.lower']:.0f}, "
            f"backend or cache {inside['jax.compile']:.0f})"
            + (f" under {where}" if where else " on another thread"))
    if inside["py.gc"] > half:
        return "gc", f"{inside['py.gc']:.0f} ms of collections"
    engine = rec["engine_thread"]
    others = {name: frames for name, frames in stacks.items()
              if name not in (engine, "rtpu-llm-watch")}
    if rec["watch_late_ms"] > half:
        busy = [name for name, frames in others.items()
                if not _waits(frames)]
        said = (f"the watcher woke {rec['watch_late_ms']:.0f} ms late, the "
                f"process used {rec['watch_late_cpu_ms']:.0f} ms of CPU "
                "meanwhile")
        if not busy and rec["watch_late_cpu_ms"] < rec["watch_late_ms"] / 2:
            return "host starved", (
                said + ", and no other thread's stack is outside a wait: "
                "the process got no processor")
        return "interpreter held", said + (
            "; not in a wait: " + ", ".join(
                f"{name} at {others[name][0][0]}:{others[name][0][1]} "
                f"{others[name][0][2]}" for name in busy) if busy else
            "; the thread that held it had moved on when the stacks were "
            "taken")
    waited = lock_wait_ms(tree)
    if waited > half:
        return "lock", f"{waited:.0f} ms waiting for the engine lock"
    leaf_ms, leaf = _longest_leaf(tree)
    mine = stacks.get(engine)
    # without stacks: the leaf is a span that only the model step fills
    in_adapter = any(path == adapter_file for path, _, _ in mine) \
        if mine else leaf.startswith(("runner.", "llm.step.decode",
                                      "llm.step.prefill"))
    if in_adapter and rec["cpu_ms"] < half:
        at = next((f"{path}:{line} {func}" for path, line, func in mine
                   if path == adapter_file), leaf) if mine else leaf
        return "device or runtime", (
            f"the engine thread waited in the model step ({at}) with "
            f"{rec['cpu_ms']:.1f} ms of CPU; the watcher at most "
            f"{rec['watch_late_ms']:.0f} ms late")
    return "engine", (
        f"{leaf} took {leaf_ms:.0f} ms of its own, {rec['cpu_ms']:.1f} ms "
        f"of CPU in the step ({compiles:.0f} ms of compiles "
        f"and {inside['py.gc']:.0f} ms of collections inside it)")
