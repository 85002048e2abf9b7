"""What handing a state slot to a new sequence costs the engine thread: the program's state_admit_seconds_total (host seconds inside runner.state.admit: the slots of an admitted group zeroed by one dispatch) over state_admits_total, both since the end of the warm-up (the runner reads the counters before the load starts). None where the program has no such counters."""

NAME = "state_admit_ms_per_request.serve"
UNIT = "ms"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import jamba_views as jv
    got = jv.state_admits(obs)
    if got is None:
        return None
    n, seconds = got
    print(f"[{NAME}] {n} sequences admitted into a state slot since the "
          f"warm-up, {seconds:.3f} s of the host inside runner.state.admit",
          flush=True)
    return 1e3 * seconds / n
