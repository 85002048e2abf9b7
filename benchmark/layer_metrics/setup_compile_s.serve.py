"""Seconds of the replica's backend compile requests before the window (the compiler, or on a warm machine the compile cache's read): compile_s over the rows of __llm_metrics__()["setup"]["programs"] last met before t0; prints hits, misses and the cache's own retrieval seconds. None where the program has no such record."""

NAME = "setup_compile_s.serve"
UNIT = "s"
LAYER = "model step"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import setup_views as sv
    rows = sv.rows_before(obs)
    if rows is None:
        return None
    total = sum(r["compile_s"] for r in rows)
    counters = sv.report(obs)["counters"]
    sv.describe_rows(
        rows, lambda r: r["compile_s"],
        f"compile {total:.2f} s in {sum(r['compiles'] for r in rows)} "
        f"requests ({sum(r['cache_hits'] for r in rows)} cache hits; the "
        f"process's retrievals "
        f"{counters['compile_cache_retrieval_seconds_total']:.2f} s)")
    return total
