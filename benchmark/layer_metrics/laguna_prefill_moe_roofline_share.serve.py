"""The least time the chip could take for a prompt's routed experts (the larger of the routed layers' touched experts' weights read once at the HBM bandwidth and the window's mean (token, expert) pairs x 6 x d x d_ff FLOPs at the bf16 peak: what the prefill steps' runner.fetch spans count) over the device time of a traced prefill program under the scope moe/experts (the plan, every gather, the product and the combine: benchmark/harness/laguna_views.py). Rows padded to the 8,192 bucket are not routed; a group's padding to whole blocks is work done and not required, so it lowers the share."""

NAME = "laguna_prefill_moe_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs_laguna as cl, laguna_views as lv, \
        program_spans as ps
    took = lv.prefill_scope_ms(obs, ("moe/experts",))
    touched, pairs, rows, products = [], [], [], set()
    for step in ps.window_steps(obs) or ():
        for p in ps.named(step, "llm.step.prefill"):
            for s in ps.named(p, "runner.dispatch"):
                products.add(s.get("attrs", {}).get("expert_product"))
            for s in ps.named(p, ps.RUNNER_FETCH):
                a = s.get("attrs", {})
                if "experts_touched" in a and "expert_tokens" in a:
                    touched.append(a["experts_touched"])
                    pairs.append(a["expert_tokens"])
                    # a program older than the counter: left out
                    rows.append(a.get("expert_rows_multiplied"))
    if took is None or not pairs or obs.peaks is None:
        return None
    c = obs.config["model"]["kwargs"]
    n = float(len(pairs))
    weights_s = sum(touched) / n * cl.expert_params(c) * 2 \
        / obs.peaks["hbm_bytes_per_s"]
    flops_s = sum(pairs) / n * 2.0 * cl.expert_params(c) \
        / obs.peaks["bf16_flops_per_s"]
    multiplied = [r for r in rows if r is not None]
    print(f"[{NAME}] {n:.0f} prefill steps in the window: mean "
          f"{sum(touched) / n:.0f} (expert, layer) pairs touched, "
          f"{sum(pairs) / n:.0f} (token, expert) pairs; least time "
          f"{weights_s * 1e3:.2f} ms by the weights, {flops_s * 1e3:.2f} ms "
          f"by the FLOPs; measured {took:.2f} ms a prefill program under "
          f"moe/experts; expert_product {sorted(map(str, products))}; "
          "expert_rows_multiplied / expert_tokens "
          + (f"{sum(multiplied) / max(sum(pairs), 1):.3f}" if multiplied
             else "not said by this program"), flush=True)
    return 100.0 * max(weights_s, flops_s) / (took / 1e3)
