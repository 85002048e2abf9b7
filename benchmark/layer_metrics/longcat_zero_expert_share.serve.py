"""Assignments to a zero-compute (identity) expert over all assignments (real tokens x routed layers x experts a token) of the measured window's steps, prefill and decode, from the counts the program returns beside its tokens (runner.fetch: zero_expert_tokens, routed_tokens); 33.3% where routing is even over the router's 512 + 256 outputs."""

NAME = "longcat_zero_expert_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import longcat_views
    return longcat_views.zero_expert_share(obs)
