#!/usr/bin/env python3
"""A model of the engine's schedule under a closed loop, to place a new
cell's ``ramp_seconds`` and to say what its spread will be before chip
time is spent on it. Nothing runs on a device: a step is one decode of
all running rows and at most one prompt, each at a cost given below, and
the requests are the traffic file's own (``loadgen.length_pool`` and
``ordered``, the closed loop's seeded first cuts), so a seed here starts
the prompts a run with that seed starts.

    python benchmark/checks/schedule_model.py \\
        --traffic serve_closed96_mix8k --ramps 28,36,40,44,52,60 \\
        --decode-ms 8.4 --full-ns 9.5 --window-ns 31 --window 4096 \\
        --prompt-ms 512:15,1024:20,2048:33,4096:68,8192:186 \\
        --per-prompt-ms 10 --retire-ms 8

(the costs of smallthinker_21b_a3b as read on the chip, PERF.md, PR 43:
a decode step of 96 rows is 8.4 ms + 1.2 of the host + 9.5 ns a live
token over the full layers + 31 ns a token inside the window; it read
3,440 tokens/s and 175 requests a window where the chip read 3,370 and
174-181, and its deviation was about 0.7 of what the chip's runs read:
the host's pauses are not in it). What it is for: a closed loop hands
out its ``distinct_requests`` pairs one permutation after another, and a
window that holds most of ONE permutation spreads least; where that
falls depends on the rate, so a change that moves a cell's tokens/s
moves the best ramp. A builder's tool: the benchmark's runs never call
it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import loadgen                         # noqa: E402


def window_of(traffic, seed, ramp, seconds, cost):
    """(tokens/s, prompts started, seconds of steps with a prompt) of the
    window [ramp, ramp + seconds]."""
    lengths = loadgen.ordered(loadgen.length_pool(traffic), seed, 1)
    n = int(traffic["clients"])
    first_cut = np.random.default_rng([int(seed), 3]).uniform(0.05, 1.0, n)
    waiting = []
    for i in range(n):
        p, o = next(lengths)
        waiting.append((i, p, max(1, int(o * first_cut[i]))))
    running = {}              # caller -> [tokens held, tokens to come]
    t0, t1 = ramp, ramp + seconds
    t, tokens, starts, prompt_s = 0.0, 0, 0, 0.0
    buckets = sorted(cost["prompt"])
    while t < t1:
        step = cost["host"]
        if running:
            held = [r[0] for r in running.values()]
            step += (cost["decode"] + cost["full"] * sum(held)
                     + cost["window"] * sum(min(h, cost["span"])
                                            for h in held))
        new = None
        if waiting and len(running) < n:
            new = waiting.pop(0)
            step += (cost["prompt"][next(b for b in buckets if b >= new[1])]
                     + cost["per_prompt"])
        t += step
        inside = t0 <= t <= t1
        done = []
        for c, r in running.items():
            r[0] += 1
            r[1] -= 1
            tokens += inside
            if r[1] <= 0:
                done.append(c)
        if new is not None:
            c, p, o = new
            if inside:
                tokens, starts, prompt_s = tokens + 1, starts + 1, \
                    prompt_s + step
            if o <= 1:
                done.append(c)
            else:
                running[c] = [p + 1, o - 1]
        for c in done:          # the caller asks again at once
            running.pop(c, None)
            t += cost["retire"]
            waiting.append((c,) + next(lengths))
    return tokens / seconds, starts, prompt_s


def drivers_rule(values, rng, sets=200):
    """Mean and 90th percentile, over random sets of six, of a set's
    quartile distance over its median with the run farthest from the
    median left out."""
    out = []
    for _ in range(sets):
        s = list(rng.choice(values, 6, replace=False))
        m = statistics.median(s)
        s.remove(max(s, key=lambda v: abs(v - m)))
        q = statistics.quantiles(s, n=4)
        out.append((q[2] - q[0]) / statistics.median(s))
    return float(np.mean(out)), float(np.quantile(out, 0.9))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True,
                    help="a file of benchmark/traffic, without .json")
    ap.add_argument("--ramps", default="", help="seconds, comma separated "
                    "(default: the file's own ramp_seconds)")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seeds", type=int, default=40)
    ap.add_argument("--decode-ms", type=float, required=True,
                    help="a decode step of no context")
    ap.add_argument("--host-ms", type=float, default=1.2)
    ap.add_argument("--full-ns", type=float, default=0.0,
                    help="a live token of a row, all full layers")
    ap.add_argument("--window-ns", type=float, default=0.0,
                    help="a token of a row inside --window, all window "
                    "layers")
    ap.add_argument("--window", type=int, default=1 << 30)
    ap.add_argument("--prompt-ms", required=True,
                    help="bucket:ms, comma separated")
    ap.add_argument("--per-prompt-ms", type=float, default=0.0)
    ap.add_argument("--retire-ms", type=float, default=0.0)
    a = ap.parse_args()
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           a.traffic + ".json")) as f:
        traffic = json.load(f)
    cost = {"host": a.host_ms / 1e3, "decode": a.decode_ms / 1e3,
            "full": a.full_ns / 1e9, "window": a.window_ns / 1e9,
            "span": a.window, "per_prompt": a.per_prompt_ms / 1e3,
            "retire": a.retire_ms / 1e3,
            "prompt": {int(k): float(v) / 1e3 for k, v in (
                kv.split(":") for kv in a.prompt_ms.split(","))}}
    ramps = [float(r) for r in a.ramps.split(",") if r] or [
        float(traffic["ramp_seconds"])]
    rng = np.random.default_rng(0)
    seeds = [4300000000 + 1009 * i for i in range(a.seeds)]
    for ramp in ramps:
        runs = [window_of(traffic, s, ramp, a.seconds, cost) for s in seeds]
        rate = [r[0] for r in runs]
        mean, q90 = drivers_rule(rate, rng)
        print(f"ramp {ramp:5.0f} s: tokens/s median "
              f"{statistics.median(rate):8.1f}, deviation "
              f"{100 * np.std(rate) / np.mean(rate):.2f}%, by the driver's "
              f"rule over sets of six {100 * mean:.2f}% (a tenth of the "
              f"sets over {100 * q90:.2f}%); prompts started "
              f"{min(r[1] for r in runs)}-{max(r[1] for r in runs)}, their "
              f"steps {np.mean([r[2] for r in runs]):.2f} s "
              f"(deviation {np.std([r[2] for r in runs]):.2f})", flush=True)


if __name__ == "__main__":
    main()
