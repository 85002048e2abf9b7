"""Seconds the replica spent tracing functions and lowering them to StableHLO (the Mosaic kernels' lowering in it) before the window: over the rows of __llm_metrics__()["setup"]["programs"] last met before t0, trace_s less nested_trace_s plus lower_s. The Python side of a program's first call, which no compile cache removes. None where the program has no such record."""

NAME = "setup_trace_lower_s.serve"
UNIT = "s"
LAYER = "model step"
MOVES = "setup_s"
SOURCE = "program_counter"


def python_side(row):
    return row["trace_s"] - row["nested_trace_s"] + row["lower_s"]


def read(obs):
    from benchmark.harness import setup_views as sv
    rows = sv.rows_before(obs)
    if rows is None:
        return None
    trace = sum(r["trace_s"] - r["nested_trace_s"] for r in rows)
    lower = sum(r["lower_s"] for r in rows)
    sv.describe_rows(rows, python_side,
                     f"trace {trace:.2f} s + lower {lower:.2f} s")
    nested = sorted(rows, key=lambda r: r["nested_trace_s"], reverse=True)
    sv.note("traced inside another function's trace (in its parent's "
            "seconds): " + ", ".join(
                f"{r['fun']} {r['nested_trace_s']:.2f} s in {r['n']}"
                for r in nested[:5] if r["nested_trace_s"] > 0))
    sv.describe_first_calls(obs)
    return trace + lower
