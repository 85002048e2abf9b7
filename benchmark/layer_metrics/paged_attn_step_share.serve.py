"""Device time of the operations traced under the paged_attention_decode scope (the Pallas kernel that reads each row's live pages in place, and the layout of its query and output) over the decode steps' device time."""

NAME = "paged_attn_step_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

SCOPE = ("paged_attention_decode",)


def dispatch_counters(obs):
    """What the window's decode steps say of their attention on their
    ``runner.dispatch`` spans: steps by path, and the pages that held a
    token over the pages of the padded tables. None where the program
    says nothing of it."""
    from benchmark.harness import program_spans as ps
    paths, live, padded = {}, 0, 0
    for step in ps.window_steps(obs) or ():
        for d in ps.named(step, "llm.step.decode"):
            for s in ps.named(d, "runner.dispatch"):
                a = s.get("attrs", {})
                if "attention" in a:
                    paths[a["attention"]] = paths.get(a["attention"], 0) + 1
                    live += a["kv_pages_live"]
                    padded += a["kv_pages_padded"]
    if not paths:
        return None
    return {"steps_by_attention": paths, "kv_pages_live": live,
            "kv_pages_padded": padded,
            "live_over_padded": live / max(padded, 1)}


def read(obs):
    from benchmark.harness import decode_scopes as ds, program_spans as ps
    said = dispatch_counters(obs)
    if said is not None:
        ps.note(f"decode steps of the window by attention: "
                f"{said['steps_by_attention']}; kv_pages_live / "
                f"kv_pages_padded = {said['kv_pages_live']} / "
                f"{said['kv_pages_padded']} = {said['live_over_padded']:.4f}")
    ms = ds.scope_ms(obs, SCOPE)
    if ms is not None:
        from benchmark.harness import xplane
        n = len(ds.decode_steps(obs))
        by_op = {}
        for e, _ in ds.decode_ops(obs):
            name = xplane.short_name(e.name, 48)
            by_op[name] = by_op.get(name, 0) + e.dur
        ps.note(f"under paged_attention_decode: {ms:.3f} ms of a decode "
                f"step's {ds.step_ms(obs):.3f} ms ({n} traced steps); "
                "operations of a step, ms: " + "; ".join(
                    f"{name} {dur / 1e6 / n:.3f}" for name, dur in sorted(
                        by_op.items(), key=lambda x: -x[1])[:10]))
    return ds.scope_share(obs, SCOPE)
