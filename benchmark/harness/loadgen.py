"""The one general request generator: every serve traffic file is read
here, every serve traffic kind (closed loop, open loop) schedules what
this makes.

Steadiness: the set of (prompt length, output length) pairs, and of
arrival gaps, comes from the traffic file's own ``lengths_seed`` and is
the same for every ``--seed``; the run's seed only orders them and draws
the token ids. So two seeds do the same work in another order. A closed
loop cycles through ``distinct_requests`` pairs; an open loop's window
holds exactly ``rate_per_s`` x its length of them (``open_schedule``).
"""

from __future__ import annotations

import math
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Tuple

import numpy as np


def _draw_len(rng, spec) -> int:
    if spec["dist"] == "log_uniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        return int(min(spec["max"], max(spec["min"],
                                        round(math.exp(rng.uniform(lo, hi))))))
    if spec["dist"] == "fixed":
        return int(spec["value"])
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def length_pool(traffic: Dict[str, Any], n: int = 0, salt: int = 0
                ) -> List[Tuple[int, int]]:
    """A fixed multiset of ``n`` (prompt, output) lengths of this mix
    (``distinct_requests`` of them by default), the same for every run's
    seed."""
    rng = np.random.default_rng(
        [int(traffic["lengths_seed"]), salt] if salt
        else int(traffic["lengths_seed"]))
    out = []
    for _ in range(int(n or traffic["distinct_requests"])):
        p = _draw_len(rng, traffic["prompt_len"])
        o = _draw_len(rng, traffic["output_len"])
        o = min(o, int(traffic["max_total_len"]) - p)
        out.append((p, max(1, o)))
    return out


def gap_pool(traffic: Dict[str, Any], n: int, total_s: float, salt: int
             ) -> List[float]:
    """A fixed multiset of ``n`` Poisson inter-arrival gaps, scaled so
    that they add up to ``total_s``: every run offers exactly ``n``
    requests in that time."""
    rng = np.random.default_rng([int(traffic["lengths_seed"]) + 1, salt])
    gaps = rng.exponential(1.0, n)
    return list(gaps * (total_s / gaps.sum()))


def open_schedule(traffic: Dict[str, Any], seed: int, seconds: float
                  ) -> List[Tuple[float, int, int]]:
    """An open loop's arrivals as (seconds after the window opens,
    prompt length, output length), in time order. The ramp before the
    window and the window itself each have their own fixed multiset of
    lengths and gaps, sized from the rate (``rate_per_s`` x the time), so
    that every seed offers the window the same requests and the same
    gaps and only their order differs. (With one pool longer than a run,
    each seed met another part of it: 34 to 61 requests fell due in 45 s
    at 1.1 a second, my chip runs, PR 24.)"""
    rate, ramp = float(traffic["rate_per_s"]), float(traffic["ramp_seconds"])
    out: List[Tuple[float, int, int]] = []
    for salt, start, span in ((11, -ramp, ramp), (12, 0.0, float(seconds))):
        n = max(1, round(rate * span))
        lengths = length_pool(traffic, n, salt)
        # the last arrival falls half a mean gap before the span ends
        gaps = gap_pool(traffic, n, span * (1.0 - 0.5 / n), salt)
        rng = np.random.default_rng([int(seed), salt])
        t = start
        for gi, li in zip(rng.permutation(n), rng.permutation(n)):
            t += gaps[int(gi)]
            out.append((t,) + lengths[int(li)])
    return out


def ordered(pool: List[Any], seed: int, salt: int) -> Iterator[Any]:
    """The pool, cycle after cycle, each cycle in an order the seed
    picks."""
    cycle = 0
    while True:
        rng = np.random.default_rng([int(seed), salt, cycle])
        for i in rng.permutation(len(pool)):
            yield pool[int(i)]
        cycle += 1


def prompt_tokens(seed: int, index: int, n: int, vocab: int) -> List[int]:
    """Request ``index`` of run ``seed``: its prompt, again and again."""
    rng = np.random.default_rng([int(seed), 7, int(index)])
    return rng.integers(0, vocab, n).tolist()


def new_record(index: int, due: float, n_prompt: int, n_out: int
               ) -> Dict[str, Any]:
    return {"index": index, "due": due, "sent": None, "chunks": [],
            "tokens": [], "done": None, "error": None,
            "engine_ttft_s": None, "n_prompt": n_prompt, "n_out": n_out,
            "finish_reason": None}


def stream_request(handle, rec: Dict[str, Any], payload: Dict[str, Any],
                   cancel: threading.Event) -> None:
    """One request over ``handle.stream``, its chunk arrivals written
    into ``rec``. Whatever the serving path raises (shed, timed out,
    replica lost) is this request's outcome, never the run's."""
    rec["sent"] = time.time()
    stream = None
    try:
        stream = handle.stream(payload, assign_timeout=120.0)
        for chunk in stream:
            now = time.time()
            toks = chunk.get("tokens") or []
            if toks:
                rec["chunks"].append((now, len(toks)))
                rec["tokens"].extend(int(t) for t in toks)
            if chunk.get("done"):
                rec["done"] = now
                rec["finish_reason"] = chunk.get("finish_reason")
                rec["engine_ttft_s"] = chunk.get("ttft_s")
                break
            if cancel.is_set():
                stream.cancel()
                rec["finish_reason"] = "cancelled_by_benchmark"
                break
    except Exception as e:  # noqa: BLE001 - the request's outcome
        rec["error"] = type(e).__name__
        rec["error_text"] = str(e).split("\n")[0][:200]
        if stream is not None and not cancel.is_set():
            try:
                stream.cancel()
            except Exception:  # noqa: BLE001
                pass


def run_hooks(t0: float, hooks: List[Tuple[float, Callable[[], None]]]
              ) -> List[threading.Thread]:
    """Each (offset, fn) runs at t0 + offset on a thread of its own."""
    def at(offset, fn):
        delay = t0 + offset - time.time()
        if delay > 0:
            time.sleep(delay)
        fn()
    threads = [threading.Thread(target=at, args=h, daemon=True)
               for h in hooks]
    for t in threads:
        t.start()
    return threads


def wait_first_tokens(records, t0, t1, grace_s: float) -> None:
    """After the window: requests that were due inside it get up to
    ``grace_s`` to show their first token, so that the TTFT sample is
    not cut by the window's end."""
    deadline = time.time() + grace_s
    while time.time() < deadline:
        pending = [r for r in records
                   if t0 <= r["due"] <= t1 and r["error"] is None
                   and r["done"] is None and not r["chunks"]]
        if not pending:
            return
        time.sleep(0.05)
