"""Open loop: requests are due on a Poisson schedule at the rate the
traffic file fixes, whatever the system does; each is timed from when it
was due. Arrivals start ``ramp_seconds`` before the window so that it
opens on a system already in its steady state; the schedule is
``loadgen.open_schedule``'s."""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

from benchmark.harness import loadgen


def drive(handle, traffic, seed, seconds, vocab, hooks=(), log=print):
    schedule = loadgen.open_schedule(traffic, seed, seconds)
    cancel = threading.Event()
    records, lags = [], []
    t0 = time.time() + 0.2 + float(traffic["ramp_seconds"])
    t1 = t0 + float(seconds)
    pool = ThreadPoolExecutor(max_workers=int(traffic["client_threads"]),
                              thread_name_prefix="bench-client")

    def one(rec, payload):
        lags.append((rec["due"], (time.time() - rec["due"]) * 1e3))
        loadgen.stream_request(handle, rec, payload, cancel)

    hook_threads = loadgen.run_hooks(t0, list(hooks))
    futures = []
    for index, (offset, n_prompt, n_out) in enumerate(schedule):
        due = t0 + offset
        payload = {"tokens": loadgen.prompt_tokens(
            seed, index, n_prompt, vocab), "max_new_tokens": n_out,
            "temperature": float(traffic.get("temperature", 0.0))}
        delay = due - time.time()
        if delay > 0:
            time.sleep(delay)
        rec = loadgen.new_record(index, due, n_prompt, n_out)
        records.append(rec)
        futures.append(pool.submit(one, rec, payload))
    while time.time() < t1:
        time.sleep(min(0.25, max(0.0, t1 - time.time())))
    loadgen.wait_first_tokens(records, t0, t1,
                              float(traffic["first_token_grace_seconds"]))
    cancel.set()
    pool.shutdown(wait=True, cancel_futures=True)
    for f in futures:
        if not f.cancelled() and f.exception() is not None:
            log(f"[open loop] client raised outside a request: "
                f"{f.exception()!r}")
    for t in hook_threads:
        t.join(timeout=60.0)
    return {"records": records, "t0": t0, "t1": t1,
            "gen_lag_ms": [lag for d, lag in lags if t0 <= d <= t1]}
