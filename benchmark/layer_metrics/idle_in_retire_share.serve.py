"""Share of the traced window in which the chip was idle inside llm.step.retire annotations: the wait for the step in flight, the releases and the records of a finished request. A part of idle_in_engine_share.serve (retire lies outside every runner.build_inputs / dispatch / fetch), not a fourth share beside the three. None without such annotations."""

NAME = "idle_in_retire_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import program_spans as ps, step_cover as sc
    share = sc.idle_share_in(obs, sc.RETIRE)
    if share is None:
        return None
    shares = ps.idle_shares(obs) or {}
    inner = {n: sc.idle_share_in(obs, n) for n in (sc.WAIT, sc.RELEASE)}
    sc.note(f"idle inside llm.step.retire {share:.3f}% of the traced window"
            f" (a part of idle_in_engine_share.serve "
            f"{shares.get('engine', float('nan')):.3f}%); inside "
            + ", ".join(f"{n} {v:.3f}%" for n, v in inner.items()
                        if v is not None))
    return share
