"""Over the window's decode steps, the window group's pages the running sequences held (rings taken by need: min(ring, pages of prompt + budget) a sequence) over the pages their whole rings would be (window / block_size + 1 a sequence): from the dispatch spans' kv_window_pages_held and kv_window_pages_whole_rings. 100% where every sequence runs past its window."""

NAME = "kv_ring_held_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import smallthinker_views as sv
    return sv.ring_held_share(obs)
