"""End-to-end distributed tracing: typed spans + critical-path analysis.

Reference analogue: Dapper-style request tracing
(util/tracing/tracing_helper.py propagates OpenTelemetry context through
TaskSpecs in the reference; the dashboard's timeline only ever renders
flat events). Here the ``trace_ctx`` that already rides every task spec
(trace_id/span_id/parent_span_id, worker.py ``_trace_ctx_for_submit``)
becomes queryable: every subsystem records *typed spans* into a bounded
per-process buffer, a background flusher ships them in batches to the
GCS (``trace_spans`` RPC → ``gcs.TraceTable``, bounded + indexed + a
visible drop counter, the PR-6 pattern), and ``get_trace`` merges them
with task-lifecycle spans synthesized from the state engine's task
records — no new instrumentation on the task hot path.

Span shape (one dict per span; only non-None fields ride the wire)::

    {"trace_id", "span_id", "parent_span_id",   # linkage
     "name",                                    # human label
     "kind",     # serve.request|serve.replica|task|dag.hop|object.pull
     "phase",    # queue|schedule|dispatch|transfer|execute|deserialize
     "start_ts", "end_ts",                      # wall-clock seconds
     "status",   # ok | error | shed
     "node_id", "pid", "attrs"}

Sampling (bounds overhead end to end):
  - head sampling: ``RTPU_TRACE_SAMPLE`` in [0,1] (default 0.1, the
    Dapper stance: production tracing is sampled) decides per *trace
    id* with a deterministic hash, so every process agrees on whether
    a trace is recorded without coordination. Unsampled serve requests
    skip span recording AND context propagation — their only cost is
    two clock reads on the root span. Task-lifecycle spans are NOT
    subject to this rate: they are synthesized from the state engine's
    task events, so ``get_trace`` always explains a task.
  - tail keep: spans that FAILED or ran longer than
    ``RTPU_TRACE_SLOW_S`` (default 1.0 s) are always recorded, even
    when head-sampled out — the slow/broken tail is exactly what the
    critical-path analyzer exists for;
  - ``RTPU_TRACING=0`` disables recording entirely: no span is made
    and no trace context is propagated.

The critical-path analyzer (``critical_path``) attributes a root span's
wall time to named phases with a deepest-active-span sweep: at every
instant of the root's interval the deepest span covering it wins, so
overlapping parent/child spans never double-count and uncovered gaps
fall to the nearest enclosing span's phase. ``aggregate_critical_path``
sums the same attribution across a cohort (e.g. a game day's p99
requests).

Step spans (``step_span``) are the other half: work that belongs to an
engine step, a train step or a feed, not to one request. They are not
sampled and not shipped anywhere: each is a
``jax.profiler.TraceAnnotation`` (so it lies on the device trace's clock
whenever a profiler session is open) and a host-clock record hung under
the span open on the same thread; finished root spans land in a bounded
ring their owner reads (docs/TRACING.md, "Step spans"). ``step_event``
hangs a span whose length is known only when it is over (a collector's
pause, a compile) into the same trees; ``watch_process`` makes the
interpreter and jax report theirs, and keeps a table of the process's
programs (``programs``). ``setup_report`` is what a process did before it
was ready (docs/TRACING.md, "Before a process is ready").
"""

from __future__ import annotations

import contextlib
import functools
import gc
import os
import sys
import threading
import time
import zlib
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

PHASES = ("queue", "schedule", "dispatch", "transfer", "execute",
          "deserialize", "submit", "other")

_ROOT_PARENTS = (None, "", "root")

# ------------------------------------------------------------------ config

_enabled: Optional[bool] = None
_sample_rate: Optional[float] = None
_slow_s: Optional[float] = None
_node_id: str = ""

DEFAULT_SAMPLE_RATE = 0.1


def refresh() -> None:
    """Re-read the env knobs (tests and the bench toggle them within
    one process; the hot path must not touch os.environ per span)."""
    global _enabled, _sample_rate, _slow_s, _node_id
    _enabled = os.environ.get("RTPU_TRACING", "1") not in ("0", "false")
    try:
        _sample_rate = min(1.0, max(0.0, float(
            os.environ.get("RTPU_TRACE_SAMPLE", DEFAULT_SAMPLE_RATE))))
    except ValueError:
        _sample_rate = DEFAULT_SAMPLE_RATE
    try:
        _slow_s = float(os.environ.get("RTPU_TRACE_SLOW_S", 1.0))
    except ValueError:
        _slow_s = 1.0
    _node_id = (os.environ.get("RTPU_NODE_ID") or "")[:12]


def enabled() -> bool:
    if _enabled is None:
        refresh()
    return _enabled


def slow_s() -> float:
    """``RTPU_TRACE_SLOW_S``: what ran longer is always kept (a request's
    spans here, an engine step's record in ``serve/llm/step_watch.py``)."""
    if _slow_s is None:
        refresh()
    return _slow_s


def sampled(trace_id: Optional[str]) -> bool:
    """Deterministic head-sampling decision for one trace id: every
    process hashes the id the same way, so a trace is either recorded
    by ALL its participants or by none (no half-traces from skewed
    coin flips)."""
    if _enabled is None:
        refresh()
    if not _enabled:
        return False
    if _sample_rate >= 1.0:
        return True
    if _sample_rate <= 0.0 or not trace_id:
        return False
    h = zlib.crc32(trace_id.encode()) & 0xFFFFFFFF
    return h / 4294967296.0 < _sample_rate


# span ids: a per-process random salt + counter instead of an
# os.urandom syscall per span (several spans per serve request ride
# the hot path; the 1-core overhead gate counts every microsecond)
_id_salt = os.urandom(5).hex()
_id_lock = threading.Lock()
_id_n = 0


def new_span_id() -> str:
    global _id_n
    with _id_lock:
        _id_n += 1
        n = _id_n
    return f"{_id_salt}{n:06x}"


def new_trace_id() -> str:
    return new_span_id()


# ------------------------------------------------------------------ buffer

def _ring_cap() -> int:
    return int(os.environ.get("RTPU_TRACE_BUFFER", 8192))


def _flush_interval() -> float:
    return float(os.environ.get("RTPU_TRACE_FLUSH_S", 0.5))


_lock = threading.Lock()
_buf: List[Dict[str, Any]] = []
_dropped = 0
_flusher_started = False
_flusher_stop: Optional[threading.Event] = None
_sender: Optional[Callable[[Dict[str, Any]], bool]] = None

_BATCH_MAX = 4000


def record_span(trace_id: str, span_id: str, name: str, *,
                parent_span_id: Optional[str] = None,
                kind: str = "span", phase: str = "other",
                start_ts: float, end_ts: float,
                status: str = "ok",
                attrs: Optional[Dict[str, Any]] = None) -> None:
    """Record one finished span. O(1) lock-append, never an RPC.

    Head-sampled-out spans are still kept when they are slow or broken
    (tail keep) — a partial trace for the p99.9 straggler beats a
    complete trace for the median request."""
    if not enabled():
        return
    if not sampled(trace_id) and status == "ok" \
            and (end_ts - start_ts) < _slow_s:
        return
    span = {"trace_id": trace_id, "span_id": span_id, "name": name,
            "kind": kind, "phase": phase,
            "start_ts": start_ts, "end_ts": end_ts,
            "status": status, "pid": os.getpid()}
    if parent_span_id is not None:
        span["parent_span_id"] = parent_span_id
    if attrs:
        span["attrs"] = attrs
    if _node_id:
        span["node_id"] = _node_id
    global _dropped
    with _lock:
        _buf.append(span)
        over = len(_buf) - _ring_cap()
        if over > 0:
            del _buf[:over]
            _dropped += over
    _ensure_flusher()


class Span:
    """A live span handle: start now, ``finish()`` records it.

    ``child_ctx()`` is the propagation payload (what rides a task spec,
    a serve kwarg, or a dag frame) — the receiving side parents its own
    spans under this span."""

    __slots__ = ("trace_id", "span_id", "parent_span_id", "name",
                 "kind", "phase", "start_ts", "attrs", "_done")

    def __init__(self, trace_id: str, name: str, *,
                 parent_span_id: Optional[str] = None,
                 kind: str = "span", phase: str = "other",
                 attrs: Optional[Dict[str, Any]] = None,
                 start_ts: Optional[float] = None):
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_span_id = parent_span_id
        self.name = name
        self.kind = kind
        self.phase = phase
        self.attrs = attrs
        self.start_ts = time.time() if start_ts is None else start_ts
        self._done = False

    def child_ctx(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def trace_ctx(self) -> Dict[str, str]:
        """worker.task_context-compatible ctx: submits made while this
        span is current parent under it."""
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_span_id": self.parent_span_id or "root"}

    def finish(self, status: str = "ok",
               end_ts: Optional[float] = None) -> None:
        if self._done:  # idempotent: error paths may double-finish
            return
        self._done = True
        record_span(self.trace_id, self.span_id, self.name,
                    parent_span_id=self.parent_span_id, kind=self.kind,
                    phase=self.phase, start_ts=self.start_ts,
                    end_ts=time.time() if end_ts is None else end_ts,
                    status=status, attrs=self.attrs)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.finish("error" if exc_type is not None else "ok")


def span_if(trace_id: Optional[str], name: str, **kw) -> Optional[Span]:
    """A Span when tracing is on and the trace is worth starting, else
    None (callers guard each touch with ``if s is not None``). Unlike
    ``record_span``'s tail keep, a *head* decision must be made here —
    slow/failed spans under a sampled-out trace are still caught
    because ``Span.finish`` routes through ``record_span``."""
    if not enabled() or not trace_id:
        return None
    return Span(trace_id, name, **kw)


# ------------------------------------------------------------------ flush

def drain(max_n: int = _BATCH_MAX) -> Tuple[List[Dict[str, Any]], int]:
    global _dropped
    with _lock:
        batch = _buf[:max_n]
        del _buf[:max_n]
        dropped, _dropped = _dropped, 0
    return batch, dropped


def requeue(spans: List[Dict[str, Any]], dropped: int = 0) -> None:
    global _dropped
    if not spans and not dropped:
        return
    with _lock:
        _buf[:0] = spans
        _dropped += dropped
        over = len(_buf) - _ring_cap()
        if over > 0:
            del _buf[:over]
            _dropped += over


def pending_count() -> int:
    with _lock:
        return len(_buf)


def set_sender(fn: Optional[Callable[[Dict[str, Any]], bool]]) -> None:
    global _sender
    _sender = fn


def _default_send(payload: Dict[str, Any], timeout: float = 5.0) -> bool:
    from ray_tpu._private import worker as worker_mod
    w = worker_mod._global_worker
    if w is None or not w.connected:
        return False
    try:
        w.call_sync(w.gcs, "trace_spans", payload, timeout=timeout)
        return True
    except Exception:
        return False


def flush(send_timeout: float = 5.0) -> bool:
    batch, dropped = drain()
    if not batch and not dropped:
        return True
    payload = {"spans": batch, "dropped": dropped}
    ok = (_sender(payload) if _sender is not None
          else _default_send(payload, timeout=send_timeout))
    if ok:
        return True
    requeue(batch, dropped)
    return False


def flush_all(timeout: float = 2.0) -> None:
    """Best-effort full drain (process teardown), bounded by ``timeout``
    so a dead GCS cannot stall shutdown."""
    deadline = time.monotonic() + timeout
    while pending_count():
        left = deadline - time.monotonic()
        if left <= 0 or not flush(send_timeout=max(0.1, left)):
            return


def _ensure_flusher() -> None:
    global _flusher_started, _flusher_stop
    if _flusher_started:
        return
    _flusher_started = True
    stop = _flusher_stop = threading.Event()

    def loop():
        while not stop.wait(_flush_interval()):
            try:
                flush()
            except Exception:
                pass

    threading.Thread(target=loop, daemon=True,
                     name="rtpu-trace-spans").start()


def stop_flusher() -> None:
    """Worker shutdown: stop the flusher thread and allow a later
    reconnect to start a fresh one (leaving ``_flusher_started`` set
    leaks one thread per init/shutdown cycle in tests)."""
    global _flusher_started, _flusher_stop
    if _flusher_stop is not None:
        _flusher_stop.set()
    _flusher_stop = None
    _flusher_started = False


# -------------------------------------------------------- step spans

STEP_RING = 4096

_step_tls = threading.local()
_step_roots: Deque[Dict[str, Any]] = deque(maxlen=STEP_RING)


class step_span:
    """A span of step-level work: ``with step_span("llm.step", i=3):``.

    (a) Enters ``jax.profiler.TraceAnnotation(name, **attrs)`` when jax
    is already imported in this process (it is never imported from here:
    a driver stays off the backend), so the span lies on the device
    trace's clock while a profiler session is open and costs next to
    nothing when none is. (b) Reads the host clock twice and hangs the
    finished span ``{name, t0, t1, attrs, children}`` under the span open
    on the same thread. (c) A finished root span goes to ``ring`` (the
    owner's bounded deque; the module's own when none is given, read
    with ``step_roots``). ``RTPU_TRACING=0`` turns (b) and (c) off.

    ``set(**attrs)`` adds what is known only when the work is done (how
    many were admitted, how many bytes came back); the profiler's
    annotation carries the attributes given at entry. Never keep one open
    across a generator's ``yield``: the stack of open spans is the
    thread's."""

    __slots__ = ("name", "attrs", "ring", "rec", "_ann")

    def __init__(self, name: str, ring: Optional[Deque] = None, **attrs):
        self.name = name
        self.attrs = attrs
        self.ring = ring
        self.rec: Optional[Dict[str, Any]] = None
        self._ann = None

    def set(self, **attrs) -> None:
        if self.rec is not None:
            self.rec["attrs"].update(attrs)

    def __enter__(self) -> "step_span":
        jax = sys.modules.get("jax")
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
        if enabled():
            stack = getattr(_step_tls, "stack", None)
            if stack is None:
                stack = _step_tls.stack = []
            self.rec = {"name": self.name, "t0": time.time(), "t1": None,
                        "attrs": self.attrs, "children": []}
            stack.append(self.rec)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        rec = self.rec
        if rec is not None:
            rec["t1"] = time.time()
            stack = _step_tls.stack
            stack.pop()
            if stack:
                stack[-1]["children"].append(rec)
            else:
                (_step_roots if self.ring is None else self.ring).append(rec)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)


@contextlib.contextmanager
def hung_under(rec: Optional[Dict[str, Any]]):
    """Step spans that finish in the body hang under ``rec``, a span's
    finished record (``step_span.rec``), and not under the span open on
    the thread: the end of work that the span gave away belongs to its
    tree though it comes later (a prompt's ``runner.fetch`` under the
    ``llm.step.prefill`` that dispatched its program). Such a child lies
    outside its parent's ``t0`` .. ``t1``. The profiler's annotations are
    where the body ran. ``None`` (the span was opened with
    ``RTPU_TRACING=0``): the body runs as it is."""
    if rec is None:
        yield
        return
    stack = getattr(_step_tls, "stack", None)
    if stack is None:
        stack = _step_tls.stack = []
    stack.append(rec)
    try:
        yield
    finally:
        stack.pop()


def step_roots(*names: str) -> List[Dict[str, Any]]:
    """Finished root step spans of this process that were given no ring
    of their own (the feed's, and the events of threads with no span
    open), oldest first; all of them, or those of the names given."""
    return [r for r in list(_step_roots)
            if not names or r["name"] in names]


def step_event(name: str, seconds: float, **attrs) -> None:
    """A span that is over when its length is known: ``{name, t0 = now -
    seconds, t1 = now, attrs, children: []}`` hung under the span open on
    the calling thread or, where none is open, put into the module's ring
    with the thread's name among its attributes. No profiler annotation
    (the time has passed)."""
    if not enabled():
        return
    now = time.time()
    rec = {"name": name, "t0": now - seconds, "t1": now, "attrs": attrs,
           "children": []}
    stack = getattr(_step_tls, "stack", None)
    if stack:
        rec["t0"] = max(rec["t0"], stack[-1]["t0"])
        stack[-1]["children"].append(rec)
    else:
        attrs["thread"] = threading.current_thread().name
        _step_roots.append(rec)


# what ``watch_process`` counts, whatever RTPU_TRACING says
gc_seconds_total = 0.0
gc_collections_total = 0
compiles_total = 0
compile_seconds_total = 0.0
# the Python side of a program's first call, which no compile cache
# removes; a trace inside another's trace or lowering is in its parent's
trace_seconds_total = 0.0
lower_seconds_total = 0.0
compile_cache_hits_total = 0
compile_cache_misses_total = 0
compile_cache_retrieval_seconds_total = 0.0

# the events ``watch_process`` makes the process report: the collector's
# pauses and the three stages of a program's first call
JAX_EVENTS = ("jax.trace", "jax.lower", "jax.compile")
PROCESS_EVENTS = ("py.gc",) + JAX_EVENTS

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_DURATIONS = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
                  COMPILE_EVENT: "compile", _RETRIEVAL_EVENT: "retrieval"}
# jax fires one of these inside a compile request that the persistent
# cache answered or that it compiled and WROTE to the cache, on the
# request's thread, before the request's duration
_JAX_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                     "/jax/compilation_cache/cache_misses": "miss"}

_gc_t0: Optional[float] = None      # the collection under way (one at a time)
_watching_jax = False
# depth (open traces and lowerings), cache, retrieval_s
_jax_tls = threading.local()

# one row a jitted function's name, over every trace, lowering and
# compile request of the process: what survives the rings
PROGRAMS_CAP = 512
OTHER_PROGRAMS = "(other programs)"     # the row of names over the cap
# jax.* events kept under one span (a constructor that makes its weights
# op by op dispatches thousands of programs)
EVENTS_UNDER_A_SPAN = 256
_programs: Dict[str, Dict[str, Any]] = {}
_programs_lock = threading.Lock()   # taken where jax traces or compiles


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    # runs on the thread that triggered the collection, the interpreter
    # held: every other Python thread of the process waits it out
    global _gc_t0, gc_seconds_total, gc_collections_total
    if phase == "start":
        _gc_t0 = time.perf_counter()
    elif _gc_t0 is not None:
        seconds, _gc_t0 = time.perf_counter() - _gc_t0, None
        gc_seconds_total += seconds
        gc_collections_total += 1
        step_event("py.gc", seconds, generation=info.get("generation"),
                   collected=info.get("collected"))


def _program_row(fun: str, now: float) -> Dict[str, Any]:
    row = _programs.get(fun)
    if row is None:
        if len(_programs) >= PROGRAMS_CAP:
            fun = OTHER_PROGRAMS
            row = _programs.get(fun)
        if row is None:
            row = _programs[fun] = {
                "fun": fun, "n": 0, "trace_s": 0.0, "nested_trace_s": 0.0,
                "lower_s": 0.0, "compiles": 0, "compile_s": 0.0,
                "cache_hits": 0, "cache_misses": 0, "t_first": now}
    row["t_last"] = now
    return row


def _on_jax_scalar(event: str, value: float, **_) -> None:
    # jax reports a stage's start so; its end is the duration below. A
    # lowering traces too (a rule written as a Python function)
    if event == TRACE_EVENT or event == LOWER_EVENT:
        _jax_tls.depth = getattr(_jax_tls, "depth", 0) + 1


def _on_jax_event(event: str, **_) -> None:
    said = _JAX_CACHE_EVENTS.get(event)
    if said is not None:
        _jax_tls.cache = said


def _on_jax_duration(event: str, duration: float, fun_name: str = "",
                     **_) -> None:
    global compiles_total, compile_seconds_total, trace_seconds_total, \
        lower_seconds_total, compile_cache_hits_total, \
        compile_cache_misses_total, compile_cache_retrieval_seconds_total
    kind = _JAX_DURATIONS.get(event)
    if kind is None:
        return
    tls, duration = _jax_tls, float(duration)
    if kind == "retrieval":         # inside a compile request that hit
        tls.retrieval_s = duration
        return
    now = time.time()
    # a trace names the function, the later stages ``jit(<function>)``:
    # all three go by the name the program's XLA module carries
    attrs = {"fun": "jit_" + fun_name if kind == "trace"
             else fun_name.replace("(", "_", 1).rstrip(")")}
    nested, retrieval_s = False, 0.0
    if kind != "compile":
        tls.depth = max(getattr(tls, "depth", 1) - 1, 0)
        nested = kind == "trace" and tls.depth > 0
        tls.cache = None    # (what a request that raised left behind)
    else:
        attrs["cache"] = getattr(tls, "cache", None) or "off"
        if attrs["cache"] == "hit":
            retrieval_s = getattr(tls, "retrieval_s", 0.0)
            attrs["retrieval_ms"] = retrieval_s * 1e3
        tls.cache = None
    with _programs_lock:
        row = _program_row(attrs["fun"], now)
        if kind == "trace":
            row["n"] += 1
            row["trace_s"] += duration
            if nested:
                row["nested_trace_s"] += duration
            else:
                trace_seconds_total += duration
        elif kind == "lower":
            row["lower_s"] += duration
            lower_seconds_total += duration
        else:
            row["compiles"] += 1
            row["compile_s"] += duration
            compiles_total += 1
            compile_seconds_total += duration
            if attrs["cache"] == "hit":
                row["cache_hits"] += 1
                compile_cache_hits_total += 1
                compile_cache_retrieval_seconds_total += retrieval_s
            elif attrs["cache"] == "miss":
                row["cache_misses"] += 1
                compile_cache_misses_total += 1
    if nested:      # in its parent's event, and in its own row
        return
    stack = getattr(_step_tls, "stack", None)
    if stack and len(stack[-1]["children"]) >= EVENTS_UNDER_A_SPAN:
        held = stack[-1]["attrs"]
        held["jax_events_dropped"] = held.get("jax_events_dropped", 0) + 1
        return
    step_event("jax." + kind, duration, **attrs)


def watch_process() -> None:
    """Make this process report its collector's pauses and its programs'
    first calls as step events and count them (``process_counters``,
    ``programs``): ``py.gc`` with ``generation`` and ``collected``;
    ``jax.trace``, ``jax.lower`` and ``jax.compile`` with ``fun`` (the
    name of the program's XLA module), one a stage jax went through: the
    function traced (one traced inside another's trace or lowering is
    counted in ``programs`` alone), its jaxpr lowered, and a backend
    compile request with ``cache``: ``"hit"`` (the persistent cache had
    it; ``retrieval_ms``), ``"miss"`` (compiled, and written to it) or
    ``"off"`` (neither: no cache directory, or a program under the
    cache's thresholds of size and compile time, which it never keeps).
    A call of a program that is compiled reaches none of the
    listeners. Idempotent. jax is never imported from here: a process
    that has it by then gets the listeners."""
    global _watching_jax
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    jax = sys.modules.get("jax")
    if jax is not None and not _watching_jax:
        _watching_jax = True
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        jax.monitoring.register_event_listener(_on_jax_event)
        jax.monitoring.register_scalar_listener(_on_jax_scalar)


def process_counters() -> Dict[str, Any]:
    return {"gc_seconds_total": round(gc_seconds_total, 6),
            "gc_collections_total": gc_collections_total,
            "compiles_total": compiles_total,
            "compile_seconds_total": round(compile_seconds_total, 6),
            "trace_seconds_total": round(trace_seconds_total, 6),
            "lower_seconds_total": round(lower_seconds_total, 6),
            "compile_cache_hits_total": compile_cache_hits_total,
            "compile_cache_misses_total": compile_cache_misses_total,
            "compile_cache_retrieval_seconds_total": round(
                compile_cache_retrieval_seconds_total, 6)}


def programs() -> List[Dict[str, Any]]:
    """One row a program this process traced, lowered or compiled, by
    its first event: ``{fun, n, trace_s, nested_trace_s, lower_s,
    compiles, compile_s, cache_hits, cache_misses, t_first, t_last}``.
    ``n`` counts its traces and ``compiles`` its backend compile
    requests; ``nested_trace_s`` is the part of ``trace_s`` that lay
    inside another function's trace or lowering and is in that row's
    seconds too
    (``trace_s - nested_trace_s`` sums to the time the process spent
    tracing). At most ``PROGRAMS_CAP`` names have a row; what further
    names cost is summed in the row ``OTHER_PROGRAMS``. Kept whatever
    ``RTPU_TRACING`` says."""
    with _programs_lock:
        return [dict(row) for row in _programs.values()]


# ------------------------------------------- before a process is ready

_T_IMPORT = time.time()
_process_t0: Optional[float] = None
# root spans of set-up work whose owner has no ring of its own
# (``setup_span``)
_setup_roots: Deque[Dict[str, Any]] = deque(maxlen=64)


def process_t0() -> float:
    """When the kernel started this process, on ``time.time()``'s clock
    (``/proc/self/stat``'s start time against ``/proc/uptime``, to 10
    ms); where that cannot be read, when this module was imported."""
    global _process_t0
    if _process_t0 is None:
        _process_t0 = _T_IMPORT
        try:
            with open("/proc/self/stat") as f:
                started = float(f.read().rsplit(")", 1)[1].split()[19])
            with open("/proc/uptime") as f:
                age = float(f.read().split()[0]) \
                    - started / os.sysconf("SC_CLK_TCK")
            t0 = time.time() - age
            if age >= 0.0 and t0 <= _T_IMPORT + 0.05:
                _process_t0 = t0
        except (OSError, ValueError, IndexError):
            pass
    return _process_t0


def setup_span(name: str):
    """Decorator: every call of the function runs under a root span
    ``name`` kept in the set-up ring (``setup_report()`` hands it out: a
    feed's spans do not push it out), in a process that is watched
    (``watch_process``). For set-up work whose owner keeps no ring of its
    own: the train worker's ``train.setup.*``."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            watch_process()
            with step_span(name, _setup_roots):
                return fn(*args, **kwargs)
        return run
    return wrap


def setup_report(spans: Optional[List[Dict[str, Any]]] = None,
                 first_calls=()) -> Dict[str, Any]:
    """What this process did before it was ready, as plain data:
    ``process_t0``, ``spans`` (its set-up trees: the owner's, or those of
    ``setup_span``), ``first_calls`` (the owner's record of each
    program's first call), ``programs()`` and ``process_counters()``."""
    return {"process_t0": process_t0(),
            "spans": list(_setup_roots) if spans is None else list(spans),
            "first_calls": list(first_calls), "programs": programs(),
            "counters": process_counters()}


def describe_setup(top: int = 5) -> str:
    """``setup_report()`` of a process whose set-up spans are
    ``setup_span``'s, in one line for a log: process start to the first
    span, each span, the ``top`` programs by trace + lower + compile
    seconds with what the cache said, and the feed's first batch."""
    report = setup_report()
    t0 = report["process_t0"]
    parts = []
    spans = sorted(report["spans"], key=lambda s: s["t0"])
    if spans:
        parts.append(f"process start to {spans[0]['name']} "
                     f"{spans[0]['t0'] - t0:.2f} s")
    parts += [f"{s['name']} {s['t1'] - s['t0']:.2f} s" for s in spans]
    rows = sorted(report["programs"], reverse=True, key=lambda r: (
        r["trace_s"] - r["nested_trace_s"] + r["lower_s"] + r["compile_s"]))
    c = report["counters"]
    parts.append(
        f"{len(report['programs'])} programs: trace "
        f"{c['trace_seconds_total']:.2f} s, lower "
        f"{c['lower_seconds_total']:.2f} s, compile "
        f"{c['compile_seconds_total']:.2f} s in {c['compiles_total']} "
        f"requests ({c['compile_cache_hits_total']} cache hits, "
        f"{c['compile_cache_misses_total']} misses); the largest: "
        + ", ".join(
            f"{r['fun']} {r['trace_s'] - r['nested_trace_s']:.2f} + "
            f"{r['lower_s']:.2f} + {r['compile_s']:.2f} s "
            f"({r['cache_hits']} hit, {r['cache_misses']} miss)"
            for r in rows[:top]))
    if len(_step_roots) < STEP_RING:    # else the first has left the ring
        first = next(iter(step_roots("data.feed.host_batch")), None)
        if first is not None:
            parts.append(
                f"first data.feed.host_batch {first['t1'] - first['t0']:.2f}"
                f" s, done {first['t1'] - t0:.2f} s after process start")
    return "set-up: " + "; ".join(parts)


# ------------------------------------------------- task-span synthesis

def synthesize_task_spans(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Task-lifecycle phase spans from ONE state-engine task record —
    no extra instrumentation on the submit/execute hot paths; the
    task-event pipeline already carries every timestamp this needs.

    Layout (ids derive from the propagated span id, so the task span
    slots into the trace tree exactly where ``_trace_ctx_for_submit``
    said it would)::

        <span_id>            name=<fn>      phase=submit  (whole task)
          <span_id>:queue    owner submit -> raylet queue
          <span_id>:schedule raylet queue -> worker picked
          <span_id>:dispatch worker picked -> RUNNING (push + args)
          <span_id>:execute  RUNNING -> terminal
            <span_id>:deser    arg deserialization (deser_s)
            <span_id>:ship     return shipping     (ship_s)
    """
    tc = rec.get("trace_ctx") or {}
    trace_id, span_id = tc.get("trace_id"), tc.get("span_id")
    if not trace_id or not span_id:
        return []
    st = rec.get("state_ts") or {}
    submit = st.get("PENDING_SCHEDULING") or rec.get("created_ts")
    queued = st.get("PENDING_NODE_ASSIGNMENT")
    dispatched = rec.get("dispatch_ts")
    running = st.get("RUNNING") or rec.get("start_ts")
    end = rec.get("end_ts")
    if submit is None:
        return []
    last = max(v for v in (submit, queued, dispatched, running, end)
               if v is not None)
    status = "error" if rec.get("state") == "FAILED" else (
        "ok" if end is not None else "running")
    name = rec.get("name") or rec.get("task_id", "")[:12]
    base = {"trace_id": trace_id, "kind": "task",
            "node_id": rec.get("node_id"), "pid": rec.get("worker_pid")}
    spans = [{**base, "span_id": span_id,
              "parent_span_id": tc.get("parent_span_id"),
              "name": name, "phase": "submit",
              "start_ts": submit, "end_ts": last, "status": status,
              "attrs": {"task_id": rec.get("task_id"),
                        "state": rec.get("state"),
                        "attempt": rec.get("attempt", 0)}}]

    def child(suffix, phase, t0, t1, parent=span_id):
        if t0 is None or t1 is None or t1 < t0:
            return
        spans.append({**base, "span_id": f"{span_id}:{suffix}",
                      "parent_span_id": parent,
                      "name": f"{name}:{suffix}", "phase": phase,
                      "start_ts": t0, "end_ts": t1, "status": "ok"})

    child("queue", "queue", submit, queued)
    child("schedule", "schedule", queued, dispatched or running)
    if dispatched is not None:
        child("dispatch", "dispatch", dispatched, running)
    child("execute", "execute", running, end)
    if running is not None and rec.get("deser_s"):
        child("deser", "deserialize", running,
              running + float(rec["deser_s"]), parent=f"{span_id}:execute")
    if end is not None and rec.get("ship_s"):
        child("ship", "transfer", end - float(rec["ship_s"]), end,
              parent=f"{span_id}:execute")
    return spans


# ------------------------------------------------- tree / critical path

def build_tree(spans: List[Dict[str, Any]]
               ) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """(roots, orphans). A root's parent is absent-by-design
    (None/""/"root"); an orphan names a parent that is not in the span
    set — the reconcile completeness check fails on orphans."""
    ids = {s.get("span_id") for s in spans}
    roots, orphans = [], []
    for s in spans:
        p = s.get("parent_span_id")
        if p in _ROOT_PARENTS:
            roots.append(s)
        elif p not in ids:
            orphans.append(s)
    return roots, orphans


def tree_complete(spans: List[Dict[str, Any]]) -> Tuple[bool, str]:
    """Is this span set a well-formed tree? (>=1 root, no orphans)."""
    if not spans:
        return False, "no spans"
    roots, orphans = build_tree(spans)
    if not roots:
        return False, "no root span"
    if orphans:
        return False, (f"{len(orphans)} orphan spans, e.g. "
                       f"{orphans[0].get('name')} -> missing parent "
                       f"{orphans[0].get('parent_span_id')}")
    return True, f"{len(spans)} spans, {len(roots)} root(s)"


def _depths(spans: List[Dict[str, Any]]) -> Dict[str, int]:
    by_id = {s["span_id"]: s for s in spans if s.get("span_id")}
    depths: Dict[str, int] = {}

    def depth(sid: str, hop: int = 0) -> int:
        if sid in depths:
            return depths[sid]
        s = by_id.get(sid)
        if s is None or hop > len(by_id):  # cycle guard
            return 0
        p = s.get("parent_span_id")
        d = 0 if p in _ROOT_PARENTS or p not in by_id \
            else depth(p, hop + 1) + 1
        depths[sid] = d
        return d

    for sid in by_id:
        depth(sid)
    return depths


def critical_path(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Attribute the root span's wall time to named phases.

    Sweep attribution: sort all span starts/ends; inside the root's
    interval, each time slice charges the DEEPEST active span's phase
    (ties: the most recently started). Overlap never double-counts and
    gaps fall to the enclosing span — so ``attributed_s`` always equals
    the root interval and the phase table sums to 100% of it.
    """
    spans = [s for s in spans
             if s.get("start_ts") is not None
             and s.get("end_ts") is not None
             and s["end_ts"] >= s["start_ts"]]
    if not spans:
        return {"total_s": 0.0, "phases": {}, "segments": [],
                "attributed_s": 0.0}
    roots, _ = build_tree(spans)
    if not roots:  # orphan-only set: attribute over the envelope
        t0 = min(s["start_ts"] for s in spans)
        t1 = max(s["end_ts"] for s in spans)
    else:
        t0 = min(r["start_ts"] for r in roots)
        t1 = max(r["end_ts"] for r in roots)
    depths = _depths(spans)
    events: List[Tuple[float, int, int]] = []
    for i, s in enumerate(spans):
        events.append((max(s["start_ts"], t0), 0, i))
        events.append((min(s["end_ts"], t1), 1, i))
    events.sort(key=lambda e: (e[0], e[1]))
    active: Dict[int, None] = {}
    phases: Dict[str, float] = {}
    segments: List[Dict[str, Any]] = []
    prev = t0

    def charge(upto: float):
        nonlocal prev
        if upto <= prev or not active:
            prev = max(prev, upto)
            return
        # deepest active span wins; among equals the latest start
        i = max(active, key=lambda j: (depths.get(
            spans[j].get("span_id", ""), 0), spans[j]["start_ts"]))
        s = spans[i]
        phase = s.get("phase") or "other"
        phases[phase] = phases.get(phase, 0.0) + (upto - prev)
        if segments and segments[-1]["span_id"] == s.get("span_id") \
                and abs(segments[-1]["t1"] - prev) < 1e-9:
            segments[-1]["t1"] = upto  # coalesce adjacent slices
        else:
            segments.append({"t0": prev, "t1": upto,
                             "span_id": s.get("span_id"),
                             "name": s.get("name"), "phase": phase})
        prev = upto

    for ts, kind, i in events:
        charge(min(max(ts, t0), t1))
        if kind == 0:
            active[i] = None
        else:
            active.pop(i, None)
    charge(t1)
    total = t1 - t0
    attributed = sum(phases.values())
    return {
        "total_s": round(total, 6),
        "attributed_s": round(attributed, 6),
        "attributed_frac": round(attributed / total, 4) if total else 0.0,
        "phases": {k: round(v, 6)
                   for k, v in sorted(phases.items(),
                                      key=lambda kv: -kv[1])},
        "segments": [{**seg, "t0": round(seg["t0"], 6),
                      "t1": round(seg["t1"], 6)} for seg in segments],
    }


def aggregate_critical_path(traces: List[List[Dict[str, Any]]]
                            ) -> Dict[str, Any]:
    """Phase attribution summed over a cohort of traces (the p99 slice
    of a game day): where does the tail actually spend its time?"""
    phases: Dict[str, float] = {}
    total = 0.0
    n = 0
    for spans in traces:
        cp = critical_path(spans)
        if not cp["phases"]:
            continue
        n += 1
        total += cp["total_s"]
        for k, v in cp["phases"].items():
            phases[k] = phases.get(k, 0.0) + v
    out = {"traces": n, "total_s": round(total, 6),
           "phases": {k: round(v, 6)
                      for k, v in sorted(phases.items(),
                                         key=lambda kv: -kv[1])}}
    if total > 0:
        out["phase_frac"] = {k: round(v / total, 4)
                             for k, v in out["phases"].items()}
    return out


# ------------------------------------------------------ chrome export

def chrome_events(spans: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Spans -> chrome-trace 'X' events (one row per process, nested by
    tree depth), wall-clock microseconds — the same time axis
    ``util/timeline.py`` and the XLA device spans merged by
    ``util/tpu_profiler.py`` already use, so the outputs concatenate
    into one chrome://tracing document."""
    depths = _depths(spans)
    out = []
    for s in spans:
        if s.get("start_ts") is None or s.get("end_ts") is None:
            continue
        out.append({
            "name": s.get("name", "?"), "ph": "X", "cat": "trace",
            "ts": s["start_ts"] * 1e6,
            "dur": max(s["end_ts"] - s["start_ts"], 0) * 1e6,
            "pid": s.get("pid") or 0,
            "tid": depths.get(s.get("span_id", ""), 0),
            "cname": "terrible" if s.get("status") == "error" else None,
            "args": {"trace_id": s.get("trace_id"),
                     "span_id": s.get("span_id"),
                     "parent_span_id": s.get("parent_span_id"),
                     "phase": s.get("phase"),
                     "kind": s.get("kind")},
        })
    return [{k: v for k, v in e.items() if v is not None} for e in out]


def export_chrome(spans: List[Dict[str, Any]],
                  device_events: Optional[List[Dict[str, Any]]] = None,
                  pad_s: float = 0.05) -> List[Dict[str, Any]]:
    """One chrome-trace document for a trace: its spans plus any XLA
    device spans (``tpu_profiler`` rows in the merged timeline, pids >=
    ``_XLA_PID_BASE``) that overlap the trace window. Pass
    ``device_events=None`` to pull the merged timeline automatically."""
    out = chrome_events(spans)
    if not out:
        return out
    t0 = min(e["ts"] for e in out) - pad_s * 1e6
    t1 = max(e["ts"] + e.get("dur", 0) for e in out) + pad_s * 1e6
    if device_events is None:
        try:
            from ray_tpu.util import timeline
            device_events = timeline.timeline_dump()
        except Exception:
            device_events = []
    from ray_tpu.util.tpu_profiler import _XLA_PID_BASE
    for e in device_events or ():
        pid = e.get("pid", 0)
        if not isinstance(pid, int) or pid < _XLA_PID_BASE:
            continue
        if e.get("ph") == "M":  # process_name rows label the XLA lanes
            out.append(e)
        elif e.get("ph") == "X" and t0 <= e.get("ts", 0) <= t1:
            out.append(e)
    return out
