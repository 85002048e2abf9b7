"""Seconds from the start of the replica's process to the start of the program's constructor (llm.setup's t0 less process_t0 of __llm_metrics__()["setup"]): the worker's start, its imports, the backend's start-up and what a deployment's subclass does first (in the cells: the weights from the seed). None where the program has no such record."""

NAME = "setup_replica_boot_s.serve"
UNIT = "s"
LAYER = "engine"
MOVES = "setup_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import setup_views as sv
    tree = sv.tree(obs)
    if tree is None:
        return None
    t0 = sv.report(obs)["process_t0"]
    sv.note(f"replica process started {obs.t0 - t0:.2f} s before the "
            f"window; llm.setup began {tree['t0'] - t0:.2f} s and ended "
            f"{tree['t1'] - t0:.2f} s after that start")
    return tree["t0"] - t0
