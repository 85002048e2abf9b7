"""Closed loop: ``clients`` callers, each streaming one request after
another. A slow system receives less load, so no queue can overflow and
every request is served whatever the seed. The first request of each
client is cut short by a seeded fraction, so that the clients are out of
step from the start, as they are in the steady state."""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np

from benchmark.harness import loadgen


def drive(handle, traffic, seed, seconds, vocab, hooks=(), log=print):
    lengths = loadgen.ordered(loadgen.length_pool(traffic), seed, 1)
    counter = itertools.count()
    take = threading.Lock()
    stop_new, cancel = threading.Event(), threading.Event()
    records = []
    t_start = time.time()
    t0 = t_start + float(traffic["ramp_seconds"])
    t1 = t0 + float(seconds)
    first_cut = np.random.default_rng([int(seed), 3]).uniform(
        0.05, 1.0, int(traffic["clients"]))

    def client(i):
        first = True
        while not stop_new.is_set():
            with take:
                index = next(counter)
                n_prompt, n_out = next(lengths)
            if first:
                n_out = max(1, int(n_out * first_cut[i]))
                first = False
            payload = {"tokens": loadgen.prompt_tokens(
                seed, index, n_prompt, vocab),
                "max_new_tokens": n_out,
                "temperature": float(traffic.get("temperature", 0.0))}
            # closed loop: a request is due when it is sent
            rec = loadgen.new_record(index, time.time(), n_prompt, n_out)
            with take:
                records.append(rec)
            loadgen.stream_request(handle, rec, payload, cancel)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(int(traffic["clients"]))]
    for t in threads:
        t.start()
    hook_threads = loadgen.run_hooks(t0, list(hooks))
    while time.time() < t1:
        time.sleep(min(0.25, max(0.0, t1 - time.time())))
    stop_new.set()
    with take:
        snapshot = list(records)
    loadgen.wait_first_tokens(snapshot, t0, t1,
                              float(traffic["first_token_grace_seconds"]))
    cancel.set()
    for t in threads + hook_threads:
        t.join(timeout=60.0)
    alive = sum(t.is_alive() for t in threads)
    if alive:
        log(f"[closed loop] {alive} client thread(s) did not stop")
    with take:
        return {"records": list(records), "t0": t0, "t1": t1,
                "gen_lag_ms": []}
