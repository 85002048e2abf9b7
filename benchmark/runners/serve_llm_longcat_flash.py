"""Runner of the LongCat-Flash serving configuration: ``serve_llm.py``'s
replica and driver, with this model's weights and reference in the places
where that file names GPT-2's. What it can share it imports, from
``serve_llm.py`` (the warm-up, the profiler hook, the window's
measurement), from ``serve_llm_kimi_linear.py`` (the steps' medians, the
key a prompt is remembered under, a relative distance) and from
``serve_llm_kimi_k2.py`` (the pick of completed requests, the split of the
window's steps, the reachable prompt range); none of them is edited. Its
own: a probe of the latent rows that every finished request left in the
pool's EIGHT sublayers (gathered by page: 17 pages a request, never a
temporary of the pool's size), what the routers' counters say of the
window, and the comparison that decides ``correct``.

The replica holds ONE copy of the weights: the program's own bfloat16
tree, which the plain reference reads and lifts to float32 a layer at a
time (two copies of 10.3 GB do not fit the chip).
"""

from __future__ import annotations

import importlib.util
import sys
import time

# A checkout whose program lacks the model (the parent of the PR that
# added it) cannot run this configuration: say so and leave at once,
# before a cluster is started (a replica whose constructor cannot import
# the model is started again and again: PERF.md, PR 28).
if importlib.util.find_spec("ray_tpu.models.longcat_flash") is None:
    sys.exit("benchmark: this checkout's program has no "
             "ray_tpu.models.longcat_flash; the configuration "
             "longcat_flash_omni cannot run on it")

from benchmark.runners import serve_llm                       # noqa: E402
from benchmark.runners.serve_llm_kimi_k2 import (             # noqa: E402
    pick_completed, reachable, say_slow_steps)
from benchmark.runners.serve_llm_kimi_linear import (         # noqa: E402
    _prompt_key, _state_err as _rel_err, say_steps)
from ray_tpu.serve.llm import LLMServer                       # noqa: E402

# The limits of `correct`, each between two chip readings (PERF.md
# section 2; all readings: my chip runs, PR 41). The reference is
# benchmark/reference/longcat_flash_ref.py, float32 at 'highest',
# teacher-forced over the whole served sequence.
# [LIMITS-LONGCAT]
# A served token's reference logit may lie this far under its row's
# maximum (logits of spread 1.57): the program's largest 0.322 over 128
# requests of 32 runs (a run's worst of four 0.19-0.32), the fp8 control's
# readings 3.80-4.48 over the four requests of a run. The statistic has a
# tail (a token whose 12th and 13th router probabilities nearly tie takes
# another expert: Kimi-K2's reached 1.16 over 112 requests where its
# median run read 0.59), so the limit leaves the program 4.7 times its
# largest reading and lies 2.5 times under the control's smallest.
GAP_LIMIT = 1.5
# The latent rows a finished request left in the pool (the scaled c and
# the rotated k_r, all eight sublayers, the last 256 positions it wrote),
# against the rows the reference would cache at those positions: norm of
# the difference over the reference's norm. The program's 0.0236-0.0265
# over 128 requests (no tail: a norm over 1.2 million values; by sublayer
# 0.0029 for the first, which sees only the token table, to 0.036 for the
# eighth), the fp8-products control's 0.388-0.399 (0.155 at ONE layer on
# the CPU, checks/test_control_longcat_flash.py). A rotation at a wrong
# position, a sublayer's rows in another's place or a latent kept
# without its scale read 0.7 and more; rows kept in 8 bits would read
# ~0.036 (their rounding alone 0.0265) and are NOT seen.
LATENT_ERR_LIMIT = 0.05


class BenchLongcatServer(serve_llm.BenchLLMServer):
    def __init__(self, model, bench, engine_config):
        import jax

        from benchmark.harness import chips, spans
        from benchmark.reference import longcat_flash_glue as glue
        t = [time.time()]
        self._bench = bench
        self._rec = spans.Recorder()
        self._rec.listen_for_compiles()
        self._bench_device = chips.device_report(bench["chips"],
                                                 bench["rehearse"])
        t.append(time.time())
        cfg = glue.model_config(bench["model"], bench.get("model_kwargs"))
        params = glue.init_for(cfg, bench["seed"])
        jax.block_until_ready(params)
        t.append(time.time())
        LLMServer.__init__(self, model, {"config": cfg, "params": params},
                           engine_config)
        self._warm_seqs = []
        self._wrap_adapter()
        jax.block_until_ready(list(self.adapter._arrays.values()))
        t.append(time.time())
        self._construct_s = dict(zip(("backend", "weights", "engine"), (
            round(b - a, 2) for a, b in zip(t, t[1:]))))

    def _wrap_adapter(self):
        """``serve_llm``'s spans round the adapter's calls (with the
        calls' own arguments passed on: the engine asks this adapter for
        tokens in place of logits), and: the pages that hold the last
        rows every finishing sequence wrote are copied to the host before
        they are given back (one small program that gathers
        ``LATENT_TAIL / block_size + 1`` whole pages of all the
        sublayers, 2.8 MB a finished request, warmed with the warm-up's
        own sequences; the rows are cut out of them on the host), under
        the sequence's prompt."""
        import jax
        import numpy as np

        from benchmark.reference.longcat_flash_ref import LATENT_TAIL
        rec, adapter = self._rec, self.adapter
        prefill, decode, release = (adapter.prefill, adapter.decode,
                                    adapter.release)
        width = adapter.cfg.kv_lora_rank + adapter.cfg.qk_rope_head_dim
        by_page = jax.jit(lambda pages, page: pages[:, page])
        self._prompt_of, self._probes = {}, {}

        def traced_prefill(seqs, **kwargs):
            for s in seqs:
                self._prompt_of[s.seq_id] = _prompt_key(s.prompt)
            with rec.span("adapter.prefill", n=len(seqs),
                          tokens=sum(len(s.prompt) for s in seqs)):
                return prefill(seqs, **kwargs)

        def traced_decode(seqs, **kwargs):
            with rec.span("adapter.decode", n=len(seqs),
                          live_tokens=sum(s.total_len for s in seqs)):
                return decode(seqs, **kwargs)

        def probing_release(seq_id):
            key = self._prompt_of.pop(seq_id, None)
            st = adapter._state.get(seq_id)
            if key is not None and st is not None:
                bs, fed = adapter.cache.block_size, st["len"]
                # the last LATENT_TAIL positions written, padded at the
                # front (one program whatever the length), and the pages
                # that hold them
                pos = np.maximum(np.arange(fed - LATENT_TAIL, fed), 0)
                first = pos[0] // bs
                table = np.asarray(st["table"], np.int32)
                held = np.zeros((LATENT_TAIL // bs + 1,), np.int32)
                span = table[first:pos[-1] // bs + 1]
                held[:len(span)] = span
                with adapter._lock:
                    got = by_page(adapter._arrays["kv_pages"], held)
                got = np.asarray(got)       # [sublayers, pages, bs, row]
                self._probes[key] = (
                    fed, got[:, pos // bs - first, pos % bs, :width])
            return release(seq_id)

        adapter.prefill, adapter.decode = traced_prefill, traced_decode
        adapter.release = probing_release

    def __bench_check__(self, samples, pad_to, _unused=None, control=False):
        """Teacher-force sampled served requests through the plain
        reference, here because this process holds the chip."""
        import numpy as np

        from benchmark.reference import longcat_flash_ref as ref
        sizes = ref.sizes_of(self.adapter.cfg)
        rows = []
        for s in samples:
            r = ref.served_token_gaps(
                self.adapter.params["params"], s["prompt"], s["served"],
                sizes, pad_to, control=ref.fp8 if control else None)
            want = np.asarray(r["latents"], np.float32)
            fed, got = self._probes.get(_prompt_key(s["prompt"]),
                                        (-1, None))
            n = want.shape[1]
            row = {"index": s["index"], "n": len(s["served"]),
                   "max_gap": float(np.max(r["gaps"])),
                   "argmax_equal": r["argmax_equal"],
                   "logit_std": r["logit_std"],
                   # the pool took in all but the last served token
                   "cache_tokens_ok":
                       fed == len(s["prompt"]) + len(s["served"]) - 1,
                   "latent_err": float("inf") if got is None else
                   _rel_err(got.astype(np.float32)[:, -n:], want)}
            if got is not None:     # by sublayer 2 i + j, shallowest first
                row["latent_err_by_sublayer"] = [
                    _rel_err(got.astype(np.float32)[i, -n:], want[i])
                    for i in range(len(want))]
            if control:
                row["control_fp8"] = {
                    "max_gap": float(np.max(r["control_gaps"])),
                    "latent_err": _rel_err(r["control_latents"], want)}
            rows.append(row)
        return rows

    def __bench_reseed__(self, seed):
        """New weights of the same shapes (the builder's many-seed runs
        in one set-up): the old go first, two sets do not fit."""
        from benchmark.reference import longcat_flash_glue as glue
        self.adapter.params = None
        self._probes.clear()
        self.adapter.params = glue.init_for(self.adapter.cfg, seed)
        return True


def within_limits(r) -> bool:
    """One request's numbers (or a control's in their place)."""
    return r["max_gap"] <= GAP_LIMIT and r["latent_err"] <= LATENT_ERR_LIMIT


def compare(rows, log):
    """`correct`: every sampled request within every limit, and the pool
    fed the tokens it should have been. Each number is said beside its
    limit; ``nums`` holds the worst of each, and for the control whether
    it would have passed in the program's place."""
    controls = sorted({k for r in rows for k in r
                       if k.startswith("control_")})

    def say(r):
        return (f"largest gap under the row maximum {r['max_gap']:.4f} "
                f"(limit {GAP_LIMIT}), cached latent rows' error "
                f"{r['latent_err']:.5f} (limit {LATENT_ERR_LIMIT})")
    for r in rows:
        log(f"[correct] request {r['index']}: {r['n']} served tokens, "
            f"{r['argmax_equal']} equal the reference argmax (logit std "
            f"{r['logit_std']:.3f}), pool fed the right tokens: "
            f"{r['cache_tokens_ok']}; {say(r)}; by sublayer "
            f"{[round(e, 5) for e in r.get('latent_err_by_sublayer', ())]}")
        for k in controls:
            log(f"[correct]   {k[8:]} control in its place: {say(r[k])}")
    nums = {}
    for name in ("max_gap", "latent_err"):
        nums[name] = max((r[name] for r in rows), default=None)
        for k in controls:
            nums[f"{k}_{name}"] = max(r[k][name] for r in rows)
    for k in controls:
        nums[f"{k}_passes"] = all(within_limits(r[k]) for r in rows)
    ok = bool(rows) and all(
        within_limits(r) and r["cache_tokens_ok"] for r in rows)
    return ok, nums


def say_routing(m, kw, log):
    """What the routers' counters say of the window's steps: the share of
    assignments that went to a zero-compute expert, and the real
    assignments a token that landed on the experts held here."""
    from benchmark.harness import longcat_views
    r = longcat_views.window_routing(
        m["engine_metrics"].get("step_log"), m["res"]["t0"], m["res"]["t1"])
    if r is None:
        return
    pairs = max(r["routed_assignments"], 1)
    width = kw["zero_expert_num"] + kw["n_routed_experts"]
    log(f"[serve] the window's routers: {r['routed_tokens']} tokens x "
        f"{kw['num_layers']} layers x {kw['moe_topk']}: "
        f"{r['zero_expert_tokens']} assignments to a zero-compute expert "
        f"({100.0 * r['zero_expert_tokens'] / pairs:.2f}%; even routing "
        f"gives {100.0 * kw['zero_expert_num'] / width:.2f}%), "
        f"{r['expert_tokens']} to the {kw['experts_held'][1]} experts held "
        f"({r['expert_tokens'] * kw['moe_topk'] / pairs:.3f} a token a "
        "layer)")


def run(ctx):
    """Driver side: never touches a JAX backend."""
    from benchmark.harness import cells
    from ray_tpu import serve

    cell, log = ctx["cell"], ctx["log"]
    cfg, traffic = cell["config_data"], dict(cell["traffic_data"])
    rehearse = ctx["rehearse"]
    engine = dict(cfg["serve"]["engine"])
    model_kwargs = None
    if rehearse:
        engine = dict(cfg["rehearse"]["engine"])
        model_kwargs = cfg["rehearse"]["model_kwargs"]
        traffic.update(traffic.get("rehearse", {}))
    vocab = (model_kwargs or cfg["model"]["kwargs"])["vocab_size"]
    kind = cells.kind_module(cell)
    warm = reachable(traffic)
    bench = {"chips": cell["chips"], "rehearse": rehearse,
             "model": cfg["model"], "model_kwargs": model_kwargs,
             "seed": ctx["seed"], "warm_prompt": warm["prompt_len"]["min"]}
    options = ({} if rehearse
               else {"ray_actor_options": {"num_tpus": cell["chips"]}})
    dep = serve.deployment(
        name="bench_llm", num_replicas=1,
        max_concurrent_queries=int(cfg["serve"]["max_concurrent_queries"]),
        **options)(BenchLongcatServer)
    t_dep = time.time()
    log("[serve] deploying the replica (weights from the seed, "
        f"{engine['num_blocks']} x {engine['block_size']}-token latent "
        f"pages of two sublayers a layer, {engine['max_running']} decode "
        "slots)")
    handle = serve.run(dep.bind(cfg["serve"]["model"], bench, engine),
                       name="bench_llm", route_prefix="/bench_llm",
                       http_port=None, _blocking_timeout=float(
                           cfg["serve"]["replica_ready_timeout_s"]))
    try:
        info = serve_llm._call(handle, "__bench_info__", log=log)
        log(f"[serve] replica up in {time.time() - t_dep:.1f}s on "
            f"{info['device']} (constructor: {info['constructor_seconds']}"
            f"), compile cache {info['cache_dir']} ({info['cache_files']} "
            f"files, {info['cache_bytes'] / 2**20:.1f} MiB)")
        log(f"[serve] prompts of the multiset: {warm['prompt_len']['min']}"
            f"-{warm['prompt_len']['max']} tokens")
        serve_llm.warm_up(handle, engine, warm, log)
        runs = []
        for i, seed in enumerate(ctx.get("seeds") or [ctx["seed"]]):
            if i:
                serve_llm._call(handle, "__bench_reseed__", seed, log=log)
            trace_dir = ctx["trace_dir"] if ctx["trace"] and not i else None
            m = serve_llm.measure(handle, kind, traffic, seed,
                                  ctx["seconds"], vocab, trace_dir, log)
            if not trace_dir:       # a traced run's readers say them
                say_steps(m, log)
            say_slow_steps(m, log)
            say_routing(m, model_kwargs or cfg["model"]["kwargs"], log)
            samples = pick_completed(
                m["res"]["records"], seed, int(traffic["check_requests"]),
                vocab)
            rows = serve_llm._call(
                handle, "__bench_check__", samples, engine["max_seq_len"],
                None, bool(ctx.get("control")), timeout=3600.0,
                what="the reference check", log=log)
            ok, nums = compare(rows, log)
            m.update(correct=ok, check_numbers=nums, seed=seed)
            runs.append(m)
            if len(runs) > 1 or ctx.get("seeds"):
                log(f"[seeds] seed {seed}: correct={ok} {nums} "
                    f"e2e={m['e2e']} failed={m['failed']} "
                    f"attempted={m['attempted']} completed={m['completed']}")
        info = serve_llm._call(handle, "__bench_info__", log=log)
        log(f"[serve] compile cache after the run: {info['cache_files']} "
            f"files, {info['cache_bytes'] / 2**20:.1f} MiB; "
            f"{info['cache_hits']} hits, {info['cache_misses']} misses")
    finally:
        serve.shutdown()
    m = runs[0]
    obs = m["observed"]
    stats_ = obs.get("memory_stats", {})
    log(f"[serve] compile requests inside the window: "
        f"{len(obs['compiles'])}")
    log(f"[serve] device memory: peak {obs['memory_peak_bytes'] / 1e9:.3f} "
        f"GB, in use {stats_.get('bytes_in_use', 0) / 1e9:.3f} GB of "
        f"{stats_.get('bytes_limit', 0) / 1e9:.3f}")
    return {
        "correct": all(r["correct"] for r in runs),
        "attempted": m["attempted"], "failed": m["failed"],
        "window": (m["res"]["t0"], m["res"]["t1"]),
        "end_to_end": m["e2e"],
        "device": dict(info["device"],
                       memory_peak_bytes=int(obs["memory_peak_bytes"])),
        "observations": {
            "kind": "serve", "spans": obs["spans"],
            "compiles_in_window": len(obs["compiles"]),
            "window_s": m["window_s"], "records": m["res"]["records"],
            "t0": m["res"]["t0"], "t1": m["res"]["t1"],
            "gen_lag_ms": m["res"]["gen_lag_ms"],
            "engine_metrics": m["engine_metrics"],
            "trace_window_host": m["trace"], "config": cfg, "engine": engine,
            "all_runs": [{"seed": r["seed"], "correct": r["correct"],
                          "check": r["check_numbers"], "e2e": r["e2e"],
                          "failed": r["failed"],
                          "completed": r["completed"]} for r in runs]},
    }
