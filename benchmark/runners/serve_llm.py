"""Runner of an LLM serving configuration: ``serve.run`` of an
``LLMServer`` on a replica that owns the chip, clients on
``handle.stream``. ``BenchLLMServer`` is the thin subclass the benchmark
needs for now: it makes the weights from the seed, puts a span round
``adapter.prefill`` / ``adapter.decode``, warms up named shapes through
the adapter's documented contract, and starts and stops the profiler,
all inside the replica, because only the process that holds the chip
can."""

from __future__ import annotations

import os
import threading
import time

from ray_tpu.serve.llm import LLMServer

# A served token's reference logit may lie this far under its row's
# maximum (reference: benchmark/reference/gpt2_ref.py, float32 at
# 'highest', teacher-forced). Set from chip readings, PERF.md section 2:
# [LIMITS-SERVE]
GAP_LIMIT = 0.1


class BenchLLMServer(LLMServer):
    def __init__(self, model, bench, engine_config):
        import jax

        from benchmark.harness import chips, spans
        from benchmark.reference import gpt2_glue
        t = [time.time()]
        self._bench = bench
        self._rec = spans.Recorder()
        self._rec.listen_for_compiles()
        self._bench_device = chips.device_report(bench["chips"],
                                                 bench["rehearse"])
        t.append(time.time())
        cfg = gpt2_glue.model_config(bench["model"],
                                     bench.get("model_kwargs"))
        self._ref_params = gpt2_glue.init_for(cfg, bench["seed"])
        jax.block_until_ready(self._ref_params)
        t.append(time.time())
        super().__init__(model, {
            "config": cfg,
            "params": {"params": gpt2_glue.to_flax_tree(self._ref_params)}},
            engine_config)
        self._warm_seqs = []
        self._wrap_adapter()
        jax.block_until_ready(self.adapter.k_pages)
        t.append(time.time())
        self._construct_s = dict(zip(("backend", "weights", "engine"), (
            round(b - a, 2) for a, b in zip(t, t[1:]))))

    # ---- spans round the calls into the model step
    def _wrap_adapter(self):
        rec, adapter = self._rec, self.adapter
        prefill, decode = adapter.prefill, adapter.decode

        def traced_prefill(seqs):
            with rec.span("adapter.prefill", n=len(seqs),
                          tokens=sum(len(s.prompt) for s in seqs)):
                return prefill(seqs)

        def traced_decode(seqs):
            with rec.span("adapter.decode", n=len(seqs),
                          live_tokens=sum(s.total_len for s in seqs)):
                return decode(seqs)

        adapter.prefill, adapter.decode = traced_prefill, traced_decode

    # ---- methods the benchmark's driver calls over the handle
    def __bench_info__(self):
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or ""
        try:
            sizes = [os.path.getsize(os.path.join(cache_dir, f))
                     for f in os.listdir(cache_dir)]
        except OSError:
            sizes = []
        return {"device": self._bench_device, "pid": os.getpid(),
                "cache_dir": cache_dir, "cache_files": len(sizes),
                "cache_bytes": sum(sizes),
                "compiles_total": len(self._rec.compiles),
                "constructor_seconds": self._construct_s,
                "cache_hits": self._rec.cache_hits,
                "cache_misses": self._rec.cache_misses}

    def _new_seqs(self, count, n_tokens, budget):
        from ray_tpu.serve.llm.engine import SamplingParams, Sequence
        seqs = []
        for _ in range(count):
            sid = f"bench-warm-{len(self._warm_seqs) + len(seqs)}-" \
                  f"{time.time_ns()}"
            self.engine.cache.allocate(sid, n_tokens + budget)
            seqs.append(Sequence(sid, None, [1] * n_tokens,
                                 SamplingParams(max_new_tokens=budget)))
        return seqs

    def _drop(self, seqs):
        for s in seqs:
            self.adapter.release(s.seq_id)
            self.engine.cache.free(s.seq_id)

    def __bench_warm__(self, op, batch=0, length=0):
        """Warm one jitted shape through the adapter's contract
        (``prefill(seqs)``, ``decode(seqs)``, ``release``; the cache's
        ``allocate`` / ``free``) while the engine is idle."""
        t0 = time.time()
        n0 = len(self._rec.compiles)
        if op == "prefill":
            seqs = self._new_seqs(batch, length, 0)
            self.adapter.prefill(seqs)
            self._drop(seqs)
        elif op == "decode_setup":
            # `batch` live sequences, prefilled `length` at a time in
            # groups of a shape that is warm already
            while len(self._warm_seqs) < batch:
                group = self._new_seqs(
                    min(length, batch - len(self._warm_seqs)),
                    self._bench["warm_prompt"], 64)
                self.adapter.prefill(group)
                for s in group:
                    s.tokens = [1]
                self._warm_seqs.extend(group)
        elif op == "decode":
            self.adapter.decode(self._warm_seqs[:batch])
        elif op == "decode_teardown":
            self._drop(self._warm_seqs)
            self._warm_seqs = []
        else:
            raise ValueError(op)
        return {"seconds": time.time() - t0,
                "compiles": len(self._rec.compiles) - n0,
                "compile_seconds": sum(
                    c["seconds"] for c in self._rec.compiles[n0:])}

    def __bench_trace__(self, trace_dir, seconds):
        """Profile ``seconds`` of whatever the replica is doing."""
        import jax
        jax.profiler.start_trace(trace_dir)
        t0 = time.time()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                time.sleep(float(seconds))
        finally:
            t1 = time.time()
            jax.profiler.stop_trace()
        return {"t0": t0, "t1": t1}

    def __bench_observe__(self, t0, t1):
        import jax

        from benchmark.harness import chips
        out = self._rec.between(t0, t1)
        out["memory_peak_bytes"] = chips.memory_peak_bytes()
        out["memory_stats"] = {k: int(v) for k, v in (
            jax.devices()[0].memory_stats() or {}).items()
            if isinstance(v, (int, float))}
        return out

    def __bench_check__(self, samples, pad_to, ln_eps, control=False):
        """Teacher-force sampled served requests through the plain
        reference, here because this process holds the chip."""
        import numpy as np

        from benchmark.reference import gpt2_ref
        n_head = self.adapter.cfg.n_head
        rows = []
        for s in samples:
            r = gpt2_ref.served_token_gaps(
                self._ref_params, s["prompt"], s["served"], n_head, pad_to,
                with_control=gpt2_ref.fp8 if control else None,
                ln_eps=ln_eps)
            row = {"index": s["index"], "n": len(s["served"]),
                   "max_gap": float(np.max(r["gaps"])),
                   "argmax_equal": r["argmax_equal"],
                   "logit_std": r["logit_std"]}
            if control:
                row["control_max_gap"] = float(np.max(r["control_gaps"]))
            rows.append(row)
        return rows

    def __bench_reseed__(self, seed):
        """New weights of the same shapes, for the builder's many-seed
        runs in one set-up: no program compiles again."""
        from benchmark.reference import gpt2_glue
        # the old weights go first: two sets do not fit beside the pool
        # and the largest program's temporaries
        self._ref_params = self.adapter.params = None
        self._ref_params = gpt2_glue.init_for(self.adapter.cfg, seed)
        self.adapter.params = {
            "params": gpt2_glue.to_flax_tree(self._ref_params)}
        return True


# ---------------------------------------------------------------- driver

def _call(handle, method, *args, timeout=1800.0, what=None, log=print):
    """A unary call over the handle that says it is still waiting."""
    import ray_tpu
    ref = handle.options(method).remote(*args)
    t0 = time.time()
    while True:
        ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=30.0)
        if ready:
            return ray_tpu.get(ref, timeout=60.0)
        waited = time.time() - t0
        log(f"[serve] still waiting for {what or method}: {waited:.0f}s")
        if waited > timeout:
            raise TimeoutError(f"{what or method} took over {timeout}s")


def warm_up(handle, engine, traffic, log):
    """Every (batch, length) program this traffic can reach, before the
    window."""
    from benchmark.harness import buckets
    args = (traffic["prompt_len"]["min"], traffic["prompt_len"]["max"],
            engine["max_prefill_tokens"], engine["max_running"])
    # largest count of a bucket first: it compiles the program, the
    # smaller counts only the slice of their rows
    pre = sorted(buckets.prefill_shapes(*args),
                 key=lambda cs: (buckets.pad_pow2(cs[0]), cs[1], -cs[0]))
    n_programs = len(buckets.prefill_buckets(*args)) + len(
        buckets.decode_buckets(engine["max_running"]))
    dec = list(range(engine["max_running"], 0, -1))
    total = {"seconds": 0.0, "compiles": 0, "compile_seconds": 0.0}

    def step(what, *args):
        r = _call(handle, "__bench_warm__", *args, what=what, log=log)
        for k in total:
            total[k] += r[k]
        if r["compiles"] or r["seconds"] > 5:
            log(f"[serve] warm {what}: {r['seconds']:.1f}s, "
                f"{r['compiles']} compile request(s) "
                f"{r['compile_seconds']:.1f}s")

    for c, s in pre:
        step(f"prefill {c} prompt(s) of {s}", "prefill", c, s)
    # live sequences for the decode shapes come from the widest warm
    # prefill shape at the shortest length
    wide = max(b for b, _ in pre)
    step(f"decode set-up: {engine['max_running']} live sequences",
         "decode_setup", engine["max_running"], wide)
    for n in dec:
        step(f"decode {n} sequence(s)", "decode", n)
    step("decode tear-down", "decode_teardown")
    info = _call(handle, "__bench_info__", log=log)
    log(f"[serve] persistent compile cache so far: {info['cache_hits']} "
        f"hits, {info['cache_misses']} misses; {info['cache_files']} "
        f"files, {info['cache_bytes'] / 2**20:.1f} MiB in it")
    log(f"[serve] warmed {len(pre)} prefill and {len(dec)} decode row "
        f"counts ({n_programs} jitted programs) in {total['seconds']:.1f}s "
        f"({total['compiles']} compile requests, "
        f"{total['compile_seconds']:.1f}s in the compiler or its cache)")


def pick_samples(records, t0, t1, seed, n, vocab):
    import numpy as np

    from benchmark.harness import loadgen
    done = [r for r in records if r["done"] is not None
            and r["error"] is None and r["tokens"]
            and t0 <= r["due"] <= t1]
    if not done:
        done = [r for r in records if r["tokens"] and r["error"] is None]
    rng = np.random.default_rng([int(seed), 13])
    picks = [done[int(i)] for i in rng.permutation(len(done))[:n]]
    return [{"index": r["index"], "served": r["tokens"],
             "prompt": loadgen.prompt_tokens(seed, r["index"],
                                             r["n_prompt"], vocab)}
            for r in picks]


def compare(rows, log):
    worst = 0.0
    for r in rows:
        worst = max(worst, r["max_gap"])
        extra = (f"; fp8 control in its place {r['control_max_gap']:.4f}"
                 if "control_max_gap" in r else "")
        log(f"[correct] request {r['index']}: {r['n']} served tokens, "
            f"{r['argmax_equal']} equal the reference argmax, largest "
            f"gap under the row maximum {r['max_gap']:.4f} (limit "
            f"{GAP_LIMIT}, logit std {r['logit_std']:.3f}){extra}")
    nums = {"max_gap": worst}
    if rows and "control_max_gap" in rows[0]:
        nums["control_max_gap"] = max(r["control_max_gap"] for r in rows)
    return bool(rows) and worst <= GAP_LIMIT, nums


def measure(handle, kind, traffic, seed, seconds, vocab, trace_dir, log):
    """One window of traffic, and what the replica saw of it."""
    import ray_tpu

    from benchmark.harness import stats
    hooks, trace_out = [], {}
    if trace_dir:
        def trace():
            try:
                trace_out.update(_call(
                    handle, "__bench_trace__", trace_dir,
                    traffic["trace_seconds"], what="the profiler",
                    log=log))
            except Exception as e:  # noqa: BLE001 - reported, not fatal
                trace_out["error"] = repr(e)
        hooks.append((float(traffic["trace_after_seconds"]), trace))
    res = kind.drive(handle, traffic, seed, seconds, vocab, hooks=hooks,
                     log=log)
    recs, t0, t1 = res["records"], res["t0"], res["t1"]
    window_s = t1 - t0
    ttft = stats.ttft_sample_ms(recs, t0, t1)
    itl = stats.itl_sample_ms(recs, t0, t1)
    tokens = stats.tokens_in_window(recs, t0, t1)
    attempted = stats.attempted_in_window(recs, t0, t1)
    failed = stats.failed_in_window(recs, t0, t1)
    errors = {}
    for r in recs:
        if r["error"] is not None:
            errors[r["error"]] = errors.get(r["error"], 0) + 1
            if errors[r["error"]] <= 3:
                log(f"[serve] request {r['index']} failed: {r['error']}: "
                    f"{r.get('error_text')}")
    completed = sum(1 for r in recs if r["done"] is not None
                    and r["error"] is None and t0 <= r["done"] <= t1)
    log(f"[serve] window {window_s:.2f}s: {attempted} requests due, "
        f"{completed} completed inside it, {failed} failed {errors}; "
        f"{tokens} tokens delivered; {len(ttft)} TTFT and {len(itl)} gap "
        f"samples")
    log("[serve] client TTFT ms p50/p75/p90/max: " + "/".join(
        f"{stats.percentile(ttft, q) or 0:.0f}" for q in (50, 75, 90, 100))
        + "; gap ms p50/p95/p99: " + "/".join(
        f"{stats.percentile(itl, q) or 0:.1f}" for q in (50, 95, 99)))
    e2e = {"serve_tokens_per_s": tokens / window_s,
           "ttft_p90_ms": stats.percentile(ttft, 90.0),
           "itl_p95_ms": stats.percentile(itl, 95.0),
           "itl_p99_ms": stats.percentile(itl, 99.0)}
    observed = _call(handle, "__bench_observe__", t0, t1, log=log)
    engine_metrics = ray_tpu.get(
        handle.options("__llm_metrics__").remote(), timeout=120.0)
    engine_metrics.pop("token_ledger", None)
    return {"e2e": e2e, "res": res, "observed": observed,
            "engine_metrics": engine_metrics, "attempted": attempted,
            "failed": failed, "completed": completed, "errors": errors,
            "trace": trace_out, "window_s": window_s,
            "ttft_ms": ttft, "n_itl": len(itl)}


def run(ctx):
    """Driver side: never touches a JAX backend."""
    import ray_tpu
    from benchmark.harness import cells, stats
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
    bench = {"chips": cell["chips"], "rehearse": rehearse,
             "model": cfg["model"], "model_kwargs": model_kwargs,
             "seed": ctx["seed"],
             "warm_prompt": traffic["prompt_len"]["min"]}
    options = ({} if rehearse
               else {"ray_actor_options": {"num_tpus": cell["chips"]}})
    dep = serve.deployment(
        name="bench_llm", num_replicas=1,
        max_concurrent_queries=int(cfg["serve"]["max_concurrent_queries"]),
        **options)(BenchLLMServer)
    t_dep = time.time()
    log("[serve] deploying the replica (weights from the seed, "
        f"{engine['num_blocks']} x {engine['block_size']}-token pages)")
    handle = serve.run(dep.bind(cfg["serve"]["model"], bench, engine),
                       name="bench_llm", route_prefix="/bench_llm",
                       http_port=None, _blocking_timeout=float(
                           cfg["serve"]["replica_ready_timeout_s"]))
    try:
        info = _call(handle, "__bench_info__", log=log)
        log(f"[serve] replica up in {time.time() - t_dep:.1f}s on "
            f"{info['device']} (constructor: {info['constructor_seconds']}"
            f"), compile cache {info['cache_dir']} ({info['cache_files']} "
            f"files, {info['cache_bytes'] / 2**20:.1f} MiB)")
        warm_up(handle, engine, traffic, log)
        seeds = ctx.get("seeds") or [ctx["seed"]]
        rates = ctx.get("rates") or []
        if rates:                       # builder's sweep: one seed, one
            seeds = [ctx["seed"]] * len(rates)     # set-up, several rates
        runs = []
        for i, seed in enumerate(seeds):
            if rates:
                traffic["rate_per_s"] = rates[i]
                log(f"[sweep] offering {rates[i]} requests/s")
            elif i:
                _call(handle, "__bench_reseed__", seed, log=log)
            trace_dir = ctx["trace_dir"] if ctx["trace"] and not i else None
            m = measure(handle, kind, traffic, seed, ctx["seconds"], vocab,
                        trace_dir, log)
            samples = pick_samples(m["res"]["records"], m["res"]["t0"],
                                   m["res"]["t1"], seed,
                                   int(traffic["check_requests"]), vocab)
            rows = _call(handle, "__bench_check__", samples,
                         engine["max_seq_len"], cfg["ln_eps_as_run"],
                         bool(ctx.get("control")),
                         what="the reference check", log=log)
            ok, nums = compare(rows, log)
            m.update(correct=ok, check_numbers=nums, seed=seed)
            runs.append(m)
            if len(seeds) > 1:
                log(f"[seeds] seed {seed}: correct={ok} {nums} "
                    f"e2e={m['e2e']} failed={m['failed']} "
                    f"attempted={m['attempted']} completed={m['completed']}"
                    f" rate={traffic.get('rate_per_s')}")
    finally:
        serve.shutdown()
    m = runs[0]
    obs = m["observed"]
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
