"""Runner of the Kimi-K2 serving configuration: ``serve_llm.py``'s
replica and driver, with this model's weights and reference in the places
where that file names GPT-2's. What it can share it imports, from
``serve_llm.py`` (the warm-up, the profiler hook, the window's
measurement) and from
``serve_llm_kimi_linear.py`` (the steps' medians, the key a prompt is
remembered under, a relative distance); neither file is edited. Its own:
the spans round the adapter's calls, a probe of the latent rows that
every finished request left in the pool, and the comparison that decides
``correct``.

The replica holds ONE copy of the weights: the program's own bfloat16
tree, which the plain reference reads and lifts to float32 a layer at a
time (two copies of 9.7 GB do not fit the chip).
"""

from __future__ import annotations

import importlib.util
import sys
import time

# A checkout whose program lacks the model (the parent of the PR that
# added it) cannot run this configuration: say so and leave at once,
# before a cluster is started (a replica whose constructor cannot import
# the model is started again and again: PERF.md, PR 28).
if importlib.util.find_spec("ray_tpu.models.kimi_k2") is None:
    sys.exit("benchmark: this checkout's program has no "
             "ray_tpu.models.kimi_k2; the configuration kimi_k2_7_code "
             "cannot run on it")

from benchmark.runners import serve_llm                       # noqa: E402
from benchmark.runners.serve_llm_kimi_linear import (         # noqa: E402
    _prompt_key, _state_err as _rel_err, say_steps)
from ray_tpu.serve.llm import LLMServer                       # noqa: E402

# The limits of `correct`, each between two chip readings (PERF.md
# section 2; all readings: my chip runs, PR 35). The reference is
# benchmark/reference/kimi_k2_ref.py, float32 at 'highest', teacher-forced
# over the whole served sequence.
# [LIMITS-K2]
# A served token's reference logit may lie this far under its row's
# maximum (logits of spread 1.69): the program's largest 1.155 over 108
# requests of 27 runs (a heavy tail: a token whose 8th and 9th expert
# scores nearly tie takes another expert; three runs read over 1.0), the
# fp8 control's smallest reading of a run (the worst of its four
# requests) 1.706 over 10 runs. The control fails the second limit on
# every request, so this one may leave the program's tail its room.
GAP_LIMIT = 1.5
# The latent rows a finished request left in the pool (c and the rotated
# k_r, all layers, the last 256 positions it wrote), against the rows the
# reference would cache at those positions: norm of the difference over
# the reference's norm. The program's 0.0158-0.0238 over 88 requests (no
# tail: a norm over a million values), the fp8-products control's
# 0.199-0.212. A rotation at a wrong position reads ~0.7 (the k_r part is
# a quarter of a row's square norm; rows of another position read 1.41);
# rows kept in 8 bits would read ~0.034 (their rounding alone 0.0265,
# checks/test_control_kimi_k2.py: reckoned, not run on the chip).
LATENT_ERR_LIMIT = 0.03


class BenchKimiK2Server(serve_llm.BenchLLMServer):
    def __init__(self, model, bench, engine_config):
        import jax

        from benchmark.harness import chips, spans
        from benchmark.reference import kimi_k2_glue as glue
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
        tokens in place of logits), and: the last rows every finishing
        sequence wrote to the pool are copied to the host before its
        pages are given back (``_tail``: one small program and 1.8 MB a
        finished request, warmed with the warm-up's own sequences),
        under the sequence's prompt."""
        import jax
        import numpy as np

        from benchmark.reference.kimi_k2_ref import LATENT_TAIL
        rec, adapter = self._rec, self.adapter
        prefill, decode, release = (adapter.prefill, adapter.decode,
                                    adapter.release)
        width = adapter.cfg.kv_lora_rank + adapter.cfg.qk_rope_head_dim
        tail = jax.jit(lambda pages, page, slot:
                       pages[:, page, slot, :width])
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
                # front (one program whatever the length)
                pos = np.maximum(np.arange(fed - LATENT_TAIL, fed), 0)
                page = np.asarray(st["table"], np.int32)[pos // bs]
                with adapter._lock:
                    rows = tail(adapter._arrays["kv_pages"], page,
                                (pos % bs).astype(np.int32))
                self._probes[key] = (fed, np.asarray(rows))
            return release(seq_id)

        adapter.prefill, adapter.decode = traced_prefill, traced_decode
        adapter.release = probing_release

    def __bench_check__(self, samples, pad_to, _unused=None, control=False):
        """Teacher-force sampled served requests through the plain
        reference, here because this process holds the chip."""
        import numpy as np

        from benchmark.reference import kimi_k2_ref as ref
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
            if control:
                row["control_fp8"] = {
                    "max_gap": float(np.max(r["control_gaps"])),
                    "latent_err": _rel_err(r["control_latents"], want)}
            rows.append(row)
        return rows

    def __bench_reseed__(self, seed):
        """New weights of the same shapes (the builder's many-seed runs
        in one set-up): the old go first, two sets do not fit."""
        from benchmark.reference import kimi_k2_glue as glue
        self.adapter.params = None
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
            f"{r['cache_tokens_ok']}; {say(r)}")
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


def pick_completed(records, seed, n, vocab):
    """``n`` of the run's completed requests, by the seed: any request
    that ran to its end, whenever it fell due. (``serve_llm.pick_samples``
    wants requests that fell due inside the window and ended; here an
    answer takes two thirds of a window, few do, and its fallback takes
    requests the benchmark cancelled, whose pool rows run past the tokens
    the client got.)"""
    import numpy as np

    from benchmark.harness import loadgen
    done = [r for r in records if r["done"] is not None
            and r["error"] is None and r["tokens"]]
    rng = np.random.default_rng([int(seed), 13])
    return [{"index": r["index"], "served": r["tokens"],
             "prompt": loadgen.prompt_tokens(seed, r["index"],
                                             r["n_prompt"], vocab)}
            for r in (done[int(i)] for i in rng.permutation(len(done))[:n])]


def say_slow_steps(m, log):
    """How the window's time splits between decode-only steps and steps
    that carry a prefill, and how far the slowest decode-only steps lie
    over the median: tokens per second of this cell is 32 x the decode
    steps a window holds, so what varies between runs is read here."""
    from benchmark.harness import program_spans as ps, stats
    steps = ps.steps_between(m["engine_metrics"].get("step_log"),
                             m["res"]["t0"], m["res"]["t1"])
    plain = [ps.ms(s) for s in steps if not ps.named(s, "llm.step.prefill")]
    carry = [ps.ms(s) for s in steps if ps.named(s, "llm.step.prefill")]
    if plain:
        log(f"[serve] window's steps: {len(plain)} decode-only, sum "
            f"{sum(plain) / 1e3:.2f} s (ms p50/p90/p99/max "
            + "/".join(f"{stats.percentile(plain, q):.1f}"
                       for q in (50, 90, 99, 100))
            + f", mean {sum(plain) / len(plain):.2f}); {len(carry)} with a "
            f"prefill, sum {sum(carry) / 1e3:.2f} s; outside every step "
            f"{m['window_s'] - (sum(plain) + sum(carry)) / 1e3:.2f} s")


def reachable(traffic):
    """The traffic with its prompt range cut to what its fixed multiset
    of lengths holds (the same for every seed), so that the warm-up
    compiles the programs the window can reach and no other: a range of
    4,096-8,192 names the buckets (1, 4096) and (2, 4096) for a prompt
    of exactly 4,096 tokens, which the multiset does not have."""
    from benchmark.harness import loadgen
    prompts = [p for p, _ in loadgen.length_pool(traffic)]
    return dict(traffic, prompt_len=dict(
        traffic["prompt_len"], min=min(prompts), max=max(prompts)))


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
        **options)(BenchKimiK2Server)
    t_dep = time.time()
    log("[serve] deploying the replica (weights from the seed, "
        f"{engine['num_blocks']} x {engine['block_size']}-token latent "
        f"pages, {engine['max_running']} decode slots)")
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
            samples = pick_completed(
                m["res"]["records"], seed, int(traffic["check_requests"]),
                vocab)
            rows = serve_llm._call(
                handle, "__bench_check__", samples, engine["max_seq_len"],
                None, bool(ctx.get("control")),
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
