"""Over the window's decode steps whose attention is the latent kernel (the dispatch span's attention says latent_kernel), of the pages the running sequences' tables hold, those that lie in groups of 8 consecutive pages of the pool (table positions 0..7, 8..15, ...: what latent_attention_decode brings with one copy in place of eight since PR 56), counted by the allocator when it hands a table out: from the dispatch spans' kv_run_pages and kv_table_pages, the quotient of kv_run_pages_share.serve. None where no decode step of the window ran the latent kernel or the program says neither number."""

NAME = "latent_run_pages_share.serve"
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
                if a.get("attention") == "latent_kernel" \
                        and "kv_table_pages" in a:
                    run += a["kv_run_pages"]
                    held += a["kv_table_pages"]
    if not held:
        return None
    ps.note(f"latent kernel steps: kv_run_pages / kv_table_pages over the "
            f"window's decode steps = {run} / {held} = {run / held:.4f}")
    return 100.0 * run / held
