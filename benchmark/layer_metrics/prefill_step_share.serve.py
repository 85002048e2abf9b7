"""Share of the window's engine steps that carry a prefill (an llm.step.prefill span in the step's tree): the steps whose tokens itl_p99_ms reads."""

NAME = "prefill_step_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import program_spans as ps
    steps = ps.window_steps(obs)
    if not steps:
        return None
    with_prefill = sum(1 for s in steps if ps.named(s, "llm.step.prefill"))
    return 100.0 * with_prefill / len(steps)
