"""Engine steps of RTPU_TRACE_SLOW_S (1 s) or more that lie in the window: the program's own slow_steps records. Prints each record's verdict and why, its length, cpu_ms, watch_late_ms and the engine thread's innermost frames. None where the program keeps no such records."""

NAME = "slow_steps_in_window.serve"
UNIT = "count"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import step_cover as sc
    records = sc.slow_steps(obs)
    if records is None:
        return None
    m = sc.metrics(obs)
    sc.note(f"slow steps in the window: {len(records)}; since the replica "
            f"started: {m.get('slow_steps_total')} of "
            f"{m.get('steps_total')} steps")
    for r in records:
        stack = (r.get("stacks") or {}).get(r.get("engine_thread"), [])
        sc.note(f"  llm.step {r['i']}: {r['t1'] - r['t0']:.3f} s, "
                f"verdict {r['verdict']!r} ({r.get('why')}), cpu_ms "
                f"{r['cpu_ms']:.1f}, watch_late_ms "
                f"{r['watch_late_ms']:.1f}, stacks_at "
                f"{r.get('stacks_at')}, engine thread at: "
                + " <- ".join(stack[:4]))
    return float(len(records))
