"""Length of the replica's llm.setup span (LLMServer's constructor: the adapter, the weights bound and stacked, the pools and state arrays, the engine), its children printed with their bytes and the device's memory at their ends. None where the program has no such record."""

NAME = "setup_constructor_s.serve"
UNIT = "s"
LAYER = "engine"
MOVES = "setup_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import setup_views as sv
    tree = sv.tree(obs)
    if tree is None:
        return None
    for child in tree["children"]:
        if child["name"].startswith(sv.ROOT):
            sv.note(f"{child['name']} {sv.seconds(child):.3f} s "
                    f"{child['attrs']}")
    inside = sv.jax_seconds([tree])
    sv.note(f"llm.setup {sv.seconds(tree):.3f} s {tree['attrs']}; programs "
            f"inside it (also in the setup_trace_lower / setup_compile "
            f"sums): trace {inside['jax.trace']:.3f}, lower "
            f"{inside['jax.lower']:.3f}, compile {inside['jax.compile']:.3f}"
            " s")
    return sv.seconds(tree)
