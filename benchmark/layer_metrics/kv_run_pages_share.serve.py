"""Over the window's decode steps, of the pages the running sequences' tables hold (every page group), those that lie in groups of 8 consecutive pages of the pool (table positions 0..7, 8..15, ...: what paged_attention_decode brings with one copy a pool in place of eight), counted by the allocator when it hands a table out: from the dispatch spans' kv_run_pages and kv_table_pages. None where the program says neither (a program before PR 44)."""

NAME = "kv_run_pages_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import program_spans as ps
    run = held = 0
    for step in ps.window_steps(obs) or ():
        for d in ps.named(step, "llm.step.decode"):
            for s in ps.named(d, "runner.dispatch"):
                a = s.get("attrs", {})
                if "kv_table_pages" in a:
                    run += a["kv_run_pages"]
                    held += a["kv_table_pages"]
    if not held:
        return None
    ps.note(f"kv_run_pages / kv_table_pages over the window's decode "
            f"steps = {run} / {held} = {run / held:.4f}")
    return 100.0 * run / held
