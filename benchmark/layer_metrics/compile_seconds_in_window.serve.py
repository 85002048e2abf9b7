"""Seconds of backend compile requests (cache hit or not) the replica made inside the window, from the program's own jax.compile events; 0 when every shape was warmed. Prints the count and under which span each fell. None where the program records no such events."""

NAME = "compile_seconds_in_window.serve"
UNIT = "s"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import program_spans as ps, step_cover as sc
    found = sc.events(obs, sc.COMPILE)
    if found is None:
        return None
    parents = [s["name"] for st in ps.window_steps(obs) or ()
               for s in ps.walk(st)
               if any(c["name"] == sc.COMPILE for c in s["children"])]
    m = sc.metrics(obs)
    sc.note(f"compiles in the window: {len(found)}, under {parents or None}"
            f"; since the replica started {m.get('compiles_total')} "
            f"compiles, {m.get('compile_seconds_total')} s")
    return sc.seconds_inside(obs, found)
