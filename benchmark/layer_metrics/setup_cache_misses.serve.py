"""Compile requests of the replica before the window that asked the persistent compile cache and were not answered (cache_misses over the rows of __llm_metrics__()["setup"]["programs"] last met before t0); names the programs. 0 on a warm machine: over 0 says the cache did not hold this cell's programs. None where the program has no such record."""

NAME = "setup_cache_misses.serve"
UNIT = "programs"
LAYER = "model step"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import setup_views as sv
    rows = sv.rows_before(obs)
    if rows is None:
        return None
    missed = [r for r in rows if r["cache_misses"]]
    off = sum(r["compiles"] - r["cache_hits"] - r["cache_misses"]
              for r in rows)
    sv.note(f"cache misses before the window: "
            f"{sum(r['cache_misses'] for r in missed)} in {len(missed)} "
            f"programs {[r['fun'] for r in missed][:12]}; "
            f"{sum(r['cache_hits'] for r in rows)} hits; {off} requests "
            "did not ask the cache")
    return float(sum(r["cache_misses"] for r in missed))
