"""The rows that went through an expert's three products over the routed (token, expert) pairs, summed over the window's decode steps (runner.fetch: expert_rows_multiplied over expert_tokens): what the chosen routed product wastes. 8.0 for the touched form under even routing at 256 rows (every touched expert multiplies all 256 rows where 32 chose it), 4.0 for sorted blocks of 128."""

NAME = "lfm2_expert_rows_over_pairs.serve"
UNIT = "rows/pair"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import lfm2_views as lv
    return lv.rows_over_pairs(obs)
