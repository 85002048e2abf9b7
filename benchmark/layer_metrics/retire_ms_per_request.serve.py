"""What a finished request costs the engine thread: the time of the window's llm.step.retire spans over the requests retired in them (their n). Prints the split: runner.wait (the step in flight), runner.release (the adapter's release and what a deployment hooked onto it), llm.step.finalize (pages, ledger, records) with what is left, and how much of each span its children cover. None where the program has no such span."""

NAME = "retire_ms_per_request.serve"
UNIT = "ms"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import program_spans as ps, stats, step_cover as sc
    steps = ps.window_steps(obs) or ()
    retires = [s for st in steps for s in ps.named(st, sc.RETIRE)]
    n = sum(s["attrs"]["n"] for s in retires)
    if not n:
        return None
    total = sum(map(ps.ms, retires))
    wait = sum(ps.ms(c) for s in retires for c in s["children"]
               if c["name"] == sc.WAIT)
    release = sum(ps.ms(c) for s in retires for c in s["children"]
                  if c["name"] == sc.RELEASE)
    cover = [sum(ps.ms(c) for c in s["children"]) / ps.ms(s)
             for s in retires if ps.ms(s) > 0]
    finishing = sum(1 for st in steps for c in ps.named(st, "llm.step.commit")
                    if c["attrs"].get("finished", 0) > 0)
    sc.note(f"{len(retires)} llm.step.retire spans ({finishing} commits "
            f"that finished a request) retired {n} requests in "
            f"{total:.2f} ms: runner.wait {wait / n:.3f} ms a request, "
            f"runner.release {release / n:.3f}, llm.step.finalize and the rest "
            f"{(total - wait - release) / n:.3f}; a span median "
            f"{stats.median(list(map(ps.ms, retires))):.3f} ms, largest "
            f"{max(map(ps.ms, retires)):.3f}; its children cover at least "
            f"{100 * min(cover):.1f}%, median "
            f"{100 * stats.median(cover):.1f}%")
    sc.check_trees(obs)
    return total / n
