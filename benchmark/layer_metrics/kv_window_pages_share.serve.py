"""Over the window's decode steps, the pages the window group holds for the running sequences (a ring of window / block_size + 1 a sequence) over the pages their whole contexts take in a layer, which a sliding layer kept whole would hold: from the dispatch spans' kv_window_pages_held and kv_pages_live."""

NAME = "kv_window_pages_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import laguna_views as lv
    return lv.window_pages_share(obs)
