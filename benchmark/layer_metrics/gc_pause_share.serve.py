"""Share of the window in which the replica's collector ran (py.gc events of every thread: the interpreter is held throughout, so no Python thread of the replica runs). Prints the count by generation and the longest pause. None where the program records no such events."""

NAME = "gc_pause_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import step_cover as sc
    found = sc.events(obs, sc.GC)
    if found is None:
        return None
    by_gen = {}
    for e in found:
        g = e["attrs"].get("generation")
        by_gen[g] = by_gen.get(g, 0) + 1
    ring = [e for e in sc.metrics(obs)["process_events"]
            if e["name"] == sc.GC]
    m = sc.metrics(obs)
    sc.note(f"collections in the window: {len(found)} "
            f"(by generation {dict(sorted(by_gen.items()))}), "
            f"{sc.seconds_inside(obs, found) * 1e3:.2f} ms in all, longest "
            f"{max((e['t1'] - e['t0'] for e in found), default=0) * 1e3:.3f}"
            f" ms; since the replica started {m.get('gc_collections_total')}"
            f" collections, {m.get('gc_seconds_total')} s"
            + ("; THE RING LOST EVENTS OF THIS WINDOW (its oldest is later "
               "than the window start)"
               if len(ring) >= 4000 and ring[0]["t0"] > obs.t0 else ""))
    return 100.0 * sc.seconds_inside(obs, found) / (obs.t1 - obs.t0)
