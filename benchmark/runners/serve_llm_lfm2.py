"""Runner of the LFM2 serving configuration: ``serve_llm.py``'s replica
and driver, with this model's weights and reference in the places where
that file names GPT-2's. What it can share it imports, from
``serve_llm.py`` (the warm-up of every reachable shape, the profiler
hook, the window's measurement), from ``serve_llm_kimi_linear.py`` (a
relative distance, the key a prompt is remembered under, the steps'
medians), from ``serve_llm_kimi_k2.py`` (the reachable prompt range, the
pick of completed requests, the split of the window's steps) and from
``serve_llm_jamba.py`` (the counters read before the load starts, the
collector put aside, what the window's admissions cost); none of them is
edited. Its own: a probe of the K and V rows and of the convolution tail
that every finished request leaves in its pages and its slot, what the
window's routers and routed products did, and the comparison that decides
``correct``.

The replica holds ONE copy of the weights: the program's own bfloat16
tree, which the plain reference reads and lifts to float32 a layer, an
expert or a block of the vocabulary at a time.
"""

from __future__ import annotations

import importlib.util
import sys
import time

# A checkout whose program lacks the model (the parent of the PR that
# added it) cannot run this configuration: say so and leave at once,
# before a cluster is started (a replica whose constructor cannot import
# the model is started again and again: PERF.md, PR 28).
if importlib.util.find_spec("ray_tpu.models.lfm2") is None:
    sys.exit("benchmark: this checkout's program has no "
             "ray_tpu.models.lfm2; the configuration lfm2_8b_a1b cannot run "
             "on it")

from benchmark.runners import serve_llm                       # noqa: E402
from benchmark.runners.serve_llm_jamba import (               # noqa: E402
    BenchJambaServer, say_admissions)
from benchmark.runners.serve_llm_kimi_k2 import (             # noqa: E402
    pick_completed, reachable, say_slow_steps)
from benchmark.runners.serve_llm_kimi_linear import (         # noqa: E402
    _prompt_key, _state_err, say_steps)
from ray_tpu.serve.llm import LLMServer                       # noqa: E402

# The limits of `correct`, each between two chip readings (PERF.md
# section 2). The reference is benchmark/reference/lfm2_ref.py, float32
# at 'highest', teacher-forced over the whole served sequence.
# [LIMITS-LFM2] (readings: my chip runs, PR 53)
# All readings: my chip runs, PR 53 (44 requests of 11 windows for the
# program, 24 for the controls; CHANGES.md has every one). A served token
# is not the float32 reference's choice in 16-30% of positions: the four
# chosen experts weigh about a quarter each (sigmoid scores, renormalised),
# so a near-tie at the fourth place that bfloat16 decides the other way
# swaps a quarter of a layer's routed output, and fourteen routed layers
# compound it. What a maximum over a request reads of that is recorded
# and NOT judged (program 0.14-1.16, fp8 control 1.87-2.46, a weighing
# bias 0.33-1.23); what is judged is averaged over tokens or positions:
# The mean over a request's served tokens of how far the served token's
# reference logit lies under its row's maximum (logits of spread 0.905).
# Program 0.010-0.047; fp8 control 0.525-0.587.
GAP_MEAN_LIMIT = 0.15
# The K (as attended: normed, rotated) and V rows a finished request left
# in the pages of the four attention layers at its last 256 positions,
# against the rows the reference would cache there, norm of the
# difference over the reference's norm. Program 0.065-0.090 (by layer
# 0.009, 0.036-0.059, 0.070-0.097, 0.102-0.140: it grows with the routed
# layers passed); fp8 control 0.364-0.370.
KV_ERR_LIMIT = 0.18
# The same distance a POSITION (its K and V row of a layer together), the
# median over the 256 positions, in the first attention layer that has a
# routed layer before it: a changed choice of expert moves single
# positions by tens of per cent and leaves the median position alone,
# where a router that weighs by ``s + b`` moves every position alike.
# Program 0.0129-0.0144 (by layer 0.0089, 0.013-0.014, 0.030-0.059,
# 0.084-0.105); a weighing bias 0.0248-0.0345 (the one number that tells
# it from the program: its other readings lie inside the program's);
# fp8 control 0.296-0.304. The limit is the two readings' geometric mean.
KV_ROW_MEDIAN_LIMIT = 0.019
# The convolution tails it left in its slot (the last two rows of a
# convolution layer's gated input, bfloat16) against the reference's
# after the same tokens, the same distance, over the convolution layers
# BEFORE the first routed layer. Program 0.0059-0.0069; fp8 control
# 0.118-0.135. (Over all twelve layers, ``tail_err``, two tokens' rows
# read 0.019-0.207 for the program and 0.051-0.181 under a weighing bias:
# recorded, not judged.)
TAIL_ERR_LIMIT = 0.03


class BenchLfm2Server(BenchJambaServer):
    """``BenchJambaServer`` (its spans round the adapter's calls, its
    counters before the load and its collector) with this model's
    weights, probe and check."""

    def __init__(self, model, bench, engine_config):
        import jax

        from benchmark.harness import chips, spans
        from benchmark.reference import lfm2_glue as glue
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
        """``serve_llm``'s spans round the adapter's calls, and: what
        every finishing sequence leaves behind is read before its slot
        and pages are given back, under the sequence's prompt: the K and
        V rows of its last 256 positions in the four attention layers'
        pages (``probe``: [4, 2, 256, 512] bfloat16, 2 MiB) and the tail
        in its state slot (``state_of``: the slot's own rows of every
        convolution layer, [12, 2, 2048] bfloat16, 96 KiB); two small
        programs a finished request, warmed with the warm-up's own
        sequences. The reads are dispatched and their copies to the host
        started, and neither is waited for: they read slot and pages
        before any later step writes them, and the engine thread goes on
        (``serve_llm_smallthinker.py``, and for its reason)."""
        import jax
        import numpy as np

        from benchmark.reference.lfm2_ref import probe_positions
        adapter = self.adapter
        release = adapter.release
        # the spans, and the prompt a sequence is remembered under
        super()._wrap_adapter()

        def rows_at(pool, page, slot):
            # [L, n, C]. Every index an array, the layers' too: the
            # gather then reads single rows where the pool lies
            # (serve_llm_smallthinker.py, and for its reason)
            layers = jax.numpy.arange(pool.shape[0])[:, None]
            return pool[layers, page[None], slot[None]]

        probe = jax.jit(lambda k, v, page, slot: jax.numpy.stack(
            [rows_at(k, page, slot), rows_at(v, page, slot)], axis=1))

        def probing_release(seq_id):
            key = self._prompt_of.pop(seq_id, None)
            st = adapter._state.get(seq_id)
            if key is not None and st is not None:
                self._settle(keep=32)   # (the older ones: long arrived)
                bs, fed = adapter.cache.block_size, st["len"]
                where = probe_positions(fed)
                # (clipped: the warm-up decodes its sequences past their
                # budgets, into the null page)
                table = np.asarray(st["table"], np.int32)
                page = table[np.minimum(where // bs, len(table) - 1)]
                a = adapter._arrays
                with adapter._lock:
                    kv = probe(a["k_pages"], a["v_pages"], page,
                               (where % bs).astype(np.int32))
                tail = adapter.state_of(seq_id)["conv_tail"]
                tail.copy_to_host_async()
                kv.copy_to_host_async()
                self._pending.append((key, fed, tail, kv))
            return release(seq_id)

        adapter.release = probing_release

    def _settle(self, keep=0):
        """The tails whose copies were started, as host arrays; all but
        the newest ``keep``."""
        import numpy as np
        while len(self._pending) > keep:
            key, fed, tail, kv = self._pending.pop(0)
            self._probes[key] = (fed, np.asarray(tail, np.float32),
                                 np.asarray(kv, np.float32))

    def __bench_check__(self, samples, pad_to, _unused=None, control=False):
        """Teacher-force sampled served requests through the plain
        reference, here because this process holds the chip."""
        import numpy as np

        from benchmark.reference import lfm2_ref as ref
        sizes = ref.sizes_of(self.adapter.cfg)
        cfg = self.adapter.cfg
        # the convolution layers before the first routed layer
        first_routed = cfg.ffn_kinds().index("routed")
        n_dense_conv = cfg.layer_types[:first_routed].count("conv")
        # (the first routed layer's own attention comes before its experts)
        n_dense_attn = len(cfg.layer_types[:first_routed + 1]) \
            - cfg.layer_types[:first_routed + 1].count("conv")
        dense = (n_dense_conv, n_dense_attn)
        self._settle()

        rows = []
        for s in samples:
            r = ref.served_token_gaps(
                self.adapter.params["params"], s["prompt"], s["served"],
                sizes, pad_to, controls=ref.CONTROLS if control else ())
            fed, tail, kv = self._probes.get(
                _prompt_key(s["prompt"]),
                (-1, np.inf * r["tail"], np.inf * r["kv"]))
            row = dict(
                numbers(r["gaps"], tail, kv, r, *dense),
                index=s["index"], n=len(s["served"]),
                n_prompt=len(s["prompt"]), argmax_equal=r["argmax_equal"],
                logit_std=r["logit_std"],
                # slot and pages took in all but the last served token
                fed_ok=fed == r["fed"])
            for name, *_ in (ref.CONTROLS if control else ()):
                row[f"control_{name}"] = numbers(
                    r[f"control_{name}_gaps"], r[f"control_{name}_tail"],
                    r[f"control_{name}_kv"], r, *dense)
            rows.append(row)
        return rows

    def __bench_reseed__(self, seed):
        """New weights of the same shapes (the builder's many-seed runs
        in one set-up): the old go first, two sets do not fit."""
        from benchmark.reference import lfm2_glue as glue
        self.adapter.params = None
        self.adapter.params = glue.init_for(self.adapter.cfg, seed)
        return True


def numbers(gaps, tail, kv, want, n_dense_conv: int, n_dense_attn: int):
    """What is compared of one request: its served tokens' ``gaps``
    under the reference's row maxima, and the ``tail`` [n_conv, K - 1, D]
    and the rows ``kv`` [n_attn, 2, 256, row] it left, against the
    reference's (``want``); ``n_dense_conv`` convolution layers and
    ``n_dense_attn`` attention layers have no routed layer before
    them."""
    import numpy as np
    kv, ref_kv = (np.asarray(a, np.float64) for a in (kv, want["kv"]))
    # [n_attn, 256]: a position's K and V row together
    by_row = np.sqrt(np.sum((kv - ref_kv) ** 2, axis=(1, 3))
                     / np.sum(ref_kv ** 2, axis=(1, 3)))
    medians = [round(float(m), 5) for m in np.median(by_row, axis=1)]
    return {"kv_row_median": medians[min(n_dense_attn, len(medians) - 1)],
            "kv_row_median_by_layer": medians,
            "max_gap": float(np.max(gaps)),
            "mean_gap": float(np.mean(gaps)),
            "p99_gap": float(np.quantile(gaps, 0.99)),
            "gaps_over_0": float(np.mean(gaps > 0)),
            "tail_err": _state_err(tail, want["tail"]),
            "dense_tail_err": _state_err(tail[:n_dense_conv],
                                         want["tail"][:n_dense_conv]),
            "kv_err": _state_err(kv, want["kv"]),
            "kv_err_by_layer": [round(_state_err(kv[i], want["kv"][i]), 5)
                                for i in range(len(kv))]}


def within_limits(r) -> bool:
    """One request's numbers (or a control's in their place)."""
    return (r["mean_gap"] <= GAP_MEAN_LIMIT and r["kv_err"] <= KV_ERR_LIMIT
            and r["kv_row_median"] <= KV_ROW_MEDIAN_LIMIT
            and r["dense_tail_err"] <= TAIL_ERR_LIMIT)


def compare(rows, log):
    """`correct`: every sampled request within every limit, and its slot
    and pages fed the tokens they should have been. Each number is said
    beside its limit; ``nums`` holds the worst of each, and for each
    control whether it would have passed in the program's place."""
    controls = sorted({k for r in rows for k in r
                       if k.startswith("control_")})

    def say(r):
        return (f"mean gap under the row maximum {r['mean_gap']:.5f} (limit "
                f"{GAP_MEAN_LIMIT}; largest {r['max_gap']:.4f}, 99th "
                f"percentile {r['p99_gap']:.4f}, "
                f"{100 * r['gaps_over_0']:.1f}% of the tokens not the "
                f"reference's), K and V rows' error {r['kv_err']:.5f} "
                f"(limit {KV_ERR_LIMIT}; by layer {r['kv_err_by_layer']}), "
                f"a position's, the median {r['kv_row_median']:.5f} (limit "
                f"{KV_ROW_MEDIAN_LIMIT}; by layer "
                f"{r['kv_row_median_by_layer']}), "
                f"tail error before the first routed layer "
                f"{r['dense_tail_err']:.5f} (limit {TAIL_ERR_LIMIT}; all "
                f"layers {r['tail_err']:.5f})")
    for r in rows:
        log(f"[correct] request {r['index']}: prompt {r['n_prompt']}, "
            f"{r['n']} served tokens, {r['argmax_equal']} equal the "
            f"reference argmax (logit std {r['logit_std']:.3f}), slot and "
            f"pages fed the right tokens: {r['fed_ok']}; {say(r)}")
        for k in controls:
            log(f"[correct]   {k[8:]} control in its place: {say(r[k])}")
    nums = {}
    for name in ("mean_gap", "kv_err", "kv_row_median", "dense_tail_err",
                 "max_gap", "tail_err"):
        nums[name] = max((r[name] for r in rows), default=None)
        for k in controls:
            nums[f"{k}_{name}"] = max(r[k][name] for r in rows)
    for k in controls:
        nums[f"{k}_passes"] = all(within_limits(r[k]) for r in rows)
    ok = bool(rows) and all(within_limits(r) and r["fed_ok"] for r in rows)
    log(f"[correct] verdict: {ok}")
    return ok, nums


def say_routers(m, log):
    """What the window's decode steps say of their routed product: the
    form their dispatch spans name, the experts touched, the routed pairs
    and the rows multiplied (``runner.fetch``), and how uneven the load
    was."""
    from benchmark.harness import program_spans as ps, stats
    steps = ps.steps_between(m["engine_metrics"].get("step_log"),
                             m["res"]["t0"], m["res"]["t1"]) or ()
    said, touched, pairs, rows, skew = {}, [], [], [], []
    for st in steps:
        for d in ps.named(st, "llm.step.decode"):
            for sp in ps.named(d, "runner.dispatch"):
                key = str(sp.get("attrs", {}).get("expert_product"))
                said[key] = said.get(key, 0) + 1
            for sp in ps.named(d, ps.RUNNER_FETCH):
                a = sp.get("attrs", {})
                if "experts_touched" in a:
                    touched.append(a["experts_touched"])
                    pairs.append(a["expert_tokens"])
                    rows.append(a.get("expert_rows_multiplied", 0))
                    skew.append(a["moe_max_over_mean"])
    log("[serve] admissions that waited for a page group since the replica "
        f"started: {m['engine_metrics'].get('admissions_waited_total')}")
    if pairs:
        log(f"[serve] the window's routers: decode steps by expert_product "
            f"{dict(sorted(said.items()))}; median (expert, layer) pairs "
            f"touched {stats.median(touched):.0f}, routed (token, expert) "
            f"pairs {stats.median(pairs):.0f}, rows multiplied "
            f"{stats.median(rows):.0f} ({sum(rows) / max(sum(pairs), 1):.2f} "
            f"a pair), largest over mean {stats.median(skew):.2f}")


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
        **options)(BenchLfm2Server)
    t_dep = time.time()
    log("[serve] deploying the replica (weights from the seed, "
        f"{engine['num_blocks']} x {engine['block_size']}-token K and V "
        f"pages for the attention layers, {engine['max_running']} tail "
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
        settled = serve_llm._call(handle, "__bench_settle__", log=log)
        log(f"[serve] the collector's old generations put aside after the "
            f"warm-up: {settled['frozen']} objects")
        runs = []
        for i, seed in enumerate(ctx.get("seeds") or [ctx["seed"]]):
            if i:
                serve_llm._call(handle, "__bench_reseed__", seed, log=log)
            before = serve_llm._call(handle, "__bench_counters__", log=log)
            trace_dir = ctx["trace_dir"] if ctx["trace"] and not i else None
            m = serve_llm.measure(handle, kind, traffic, seed,
                                  ctx["seconds"], vocab, trace_dir, log)
            m["counters_before"] = before
            if not trace_dir:       # a traced run's readers say them
                say_steps(m, log)
            say_slow_steps(m, log)
            say_admissions(m, before, log)
            say_routers(m, log)
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
            "counters_before": m["counters_before"],
            "trace_window_host": m["trace"], "config": cfg, "engine": engine,
            "all_runs": [{"seed": r["seed"], "correct": r["correct"],
                          "check": r["check_numbers"], "e2e": r["e2e"],
                          "failed": r["failed"],
                          "completed": r["completed"]} for r in runs]},
    }
