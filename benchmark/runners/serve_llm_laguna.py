"""Runner of the Laguna serving configuration: ``serve_llm.py``'s replica
and driver, with this model's weights and reference in the places where
that file names GPT-2's. What it can share it imports, from
``serve_llm.py`` (the warm-up, the profiler hook, the window's
measurement), from ``serve_llm_kimi_linear.py`` (the steps' medians, the
key a prompt is remembered under, a relative distance) and from
``serve_llm_kimi_k2.py`` (the pick of completed requests, the split of the
window's steps, the reachable prompt range); none of them is edited. Its
own: a probe of the K and V rows that every finished request left in
BOTH page groups (the full layers' last rows, the sliding layers' whole
ring), and the comparison that decides ``correct``.

The replica holds ONE copy of the weights: the program's own bfloat16
tree, which the plain reference reads and lifts to float32 a layer at a
time (two copies of 7.7 GB do not fit beside the pools).
"""

from __future__ import annotations

import importlib.util
import sys
import time

# A checkout whose program lacks the model (the parent of the PR that
# added it) cannot run this configuration: say so and leave at once,
# before a cluster is started (a replica whose constructor cannot import
# the model is started again and again: PERF.md, PR 28).
if importlib.util.find_spec("ray_tpu.models.laguna") is None:
    sys.exit("benchmark: this checkout's program has no "
             "ray_tpu.models.laguna; the configuration laguna_xs_2 "
             "cannot run on it")

from benchmark.runners import serve_llm                       # noqa: E402
from benchmark.runners.serve_llm_kimi_k2 import (             # noqa: E402
    pick_completed, reachable, say_slow_steps)
from benchmark.runners.serve_llm_kimi_linear import (         # noqa: E402
    _prompt_key, _state_err as _rel_err, say_steps)
from ray_tpu.serve.llm import LLMServer                       # noqa: E402

# The limits of `correct`, each between two chip readings (PERF.md
# section 2; all readings: my chip runs, PR 37). The reference is
# benchmark/reference/laguna_ref.py, float32 at 'highest', teacher-forced
# over the whole served sequence.
# [LIMITS-LAGUNA]
# A served token's reference logit may lie this far under its row's
# maximum.
GAP_LIMIT = 1.2
# The K (rotated) and V rows a finished request left in the pools against
# the rows the reference would cache at those positions, norm of the
# difference over the reference's norm: the two full layers' last 256
# positions (``full_err``), and the three sliding layers' whole ring, each
# ring row against the position the ring rule puts there (``ring_err``: a
# ring indexed wrongly or a rotation at a wrong position reads ~1.4; rows
# of another sequence the same).
KV_ERR_LIMIT = 0.09


class BenchLagunaServer(serve_llm.BenchLLMServer):
    def __init__(self, model, bench, engine_config):
        import jax

        from benchmark.harness import chips, spans
        from benchmark.reference import laguna_glue as glue
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
        tokens in place of logits), and: what every finishing sequence
        left in both page groups is copied to the host before its pages
        are given back (``_probe``: one small program and 8.6 MB a
        finished request, warmed with the warm-up's own sequences), under
        the sequence's prompt."""
        import jax
        import numpy as np

        from benchmark.reference.laguna_ref import KV_TAIL
        rec, adapter = self._rec, self.adapter
        prefill, decode, release = (adapter.prefill, adapter.decode,
                                    adapter.release)
        window = adapter.cfg.sliding_window

        def probe(k_full, v_full, k_win, v_win, page, slot, ring):
            full = jax.numpy.stack(
                [k_full[:, page, slot], v_full[:, page, slot]], axis=1)
            rows = jax.numpy.stack([k_win[:, ring], v_win[:, ring]], axis=1)
            return full, rows.reshape(*rows.shape[:2], -1, rows.shape[-1])
        probe = jax.jit(probe)
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
                # the last KV_TAIL positions written, padded at the
                # front (one program whatever the length)
                pos = np.maximum(np.arange(fed - KV_TAIL, fed), 0)
                page = np.asarray(st["table"], np.int32)[pos // bs]
                a = adapter._arrays
                with adapter._lock:
                    full, ring = probe(
                        a["k_full"], a["v_full"], a["k_window"],
                        a["v_window"], page, (pos % bs).astype(np.int32),
                        np.asarray(st["rings"][window], np.int32))
                self._probes[key] = (fed, np.asarray(full),
                                     np.asarray(ring))
            return release(seq_id)

        adapter.prefill, adapter.decode = traced_prefill, traced_decode
        adapter.release = probing_release

    def __bench_check__(self, samples, pad_to, _unused=None, control=False):
        """Teacher-force sampled served requests through the plain
        reference, here because this process holds the chip."""
        import numpy as np

        from benchmark.reference import laguna_ref as ref
        sizes = ref.sizes_of(self.adapter.cfg)
        bs = self.adapter.cache.block_size
        rows = []
        for s in samples:
            r = ref.served_token_gaps(
                self.adapter.params["params"], s["prompt"], s["served"],
                sizes, pad_to, bs,
                controls=ref.CONTROLS if control else ())
            written = r["ring_written"]
            fed, full, ring = self._probes.get(_prompt_key(s["prompt"]),
                                               (-1, None, None))
            n = r["full"].shape[2]
            row = {"index": s["index"], "n": len(s["served"]),
                   "max_gap": float(np.max(r["gaps"])),
                   "argmax_equal": r["argmax_equal"],
                   "logit_std": r["logit_std"],
                   # the full group took in the prompt and all but the
                   # last served token
                   "cache_tokens_ok":
                       fed == len(s["prompt"]) + len(s["served"]) - 1,
                   "ring_rows": int(written.sum()),
                   "full_err": float("inf") if full is None else
                   _rel_err(full.astype(np.float32)[:, :, -n:], r["full"]),
                   "ring_err": float("inf") if ring is None else
                   _rel_err(ring.astype(np.float32)[:, :, written],
                            r["ring"][:, :, written])}
            if full is not None:    # by layer, shallowest first
                row["full_err_by_layer"] = [
                    _rel_err(full.astype(np.float32)[i, :, -n:],
                             r["full"][i]) for i in range(len(full))]
                row["ring_err_by_layer"] = [
                    _rel_err(ring.astype(np.float32)[i][:, written],
                             r["ring"][i][:, written])
                    for i in range(len(ring))]
            for name in ref.CONTROLS if control else ():
                row[f"control_{name}"] = {
                    "max_gap": float(np.max(r[f"control_{name}_gaps"])),
                    "full_err": _rel_err(r[f"control_{name}_full"],
                                         r["full"]),
                    "ring_err": _rel_err(
                        r[f"control_{name}_ring"][:, :, written],
                        r["ring"][:, :, written])}
            rows.append(row)
        return rows

    def __bench_reseed__(self, seed):
        """New weights of the same shapes (the builder's many-seed runs
        in one set-up): the old go first, two sets do not fit."""
        from benchmark.reference import laguna_glue as glue
        self.adapter.params = None
        self._probes.clear()
        self.adapter.params = glue.init_for(self.adapter.cfg, seed)
        return True


def within_limits(r) -> bool:
    """One request's numbers (or a control's in their place)."""
    return (r["max_gap"] <= GAP_LIMIT and r["full_err"] <= KV_ERR_LIMIT
            and r["ring_err"] <= KV_ERR_LIMIT)


def compare(rows, log):
    """`correct`: every sampled request within every limit, and the full
    group fed the tokens it should have been. Each number is said beside
    its limit; ``nums`` holds the worst of each, and for each control
    whether it would have passed in the program's place."""
    controls = sorted({k for r in rows for k in r
                       if k.startswith("control_")})

    def say(r):
        return (f"largest gap under the row maximum {r['max_gap']:.4f} "
                f"(limit {GAP_LIMIT}), cached K and V rows' error: full "
                f"layers {r['full_err']:.5f}, sliding layers' ring "
                f"{r['ring_err']:.5f} (limit {KV_ERR_LIMIT})")
    for r in rows:
        log(f"[correct] request {r['index']}: {r['n']} served tokens, "
            f"{r['argmax_equal']} equal the reference argmax (logit std "
            f"{r['logit_std']:.3f}), pools fed the right tokens: "
            f"{r['cache_tokens_ok']} ({r.get('ring_rows')} ring rows "
            f"compared); {say(r)}; by layer: full "
            f"{[round(e, 5) for e in r.get('full_err_by_layer', ())]}, ring "
            f"{[round(e, 5) for e in r.get('ring_err_by_layer', ())]}")
        for k in controls:
            log(f"[correct]   {k[8:]} control in its place: {say(r[k])}")
    nums = {}
    for name in ("max_gap", "full_err", "ring_err"):
        nums[name] = max((r[name] for r in rows), default=None)
        for k in controls:
            nums[f"{k}_{name}"] = max(r[k][name] for r in rows)
    for k in controls:
        nums[f"{k}_passes"] = all(within_limits(r[k]) for r in rows)
    ok = bool(rows) and all(
        within_limits(r) and r["cache_tokens_ok"] for r in rows)
    return ok, nums


def decode_attention(m):
    """How many of the window's decode steps ran which attention, by
    their ``runner.dispatch`` span (``"paged_kernel"`` on the chip,
    ``"gather"`` off it)."""
    from benchmark.harness import program_spans as ps
    said = {}
    for step in ps.steps_between(m["engine_metrics"].get("step_log"),
                                 m["res"]["t0"], m["res"]["t1"]):
        for d in ps.named(step, "llm.step.decode"):
            for s in ps.named(d, "runner.dispatch"):
                name = s.get("attrs", {}).get("attention")
                said[name] = said.get(name, 0) + 1
    return said


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
        **options)(BenchLagunaServer)
    t_dep = time.time()
    log("[serve] deploying the replica (weights from the seed, "
        f"{engine['num_blocks']} x {engine['block_size']}-token K and V "
        f"pages for the full layers, a ring a sequence for the sliding "
        f"ones, {engine['max_running']} decode slots)")
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
            log(f"[serve] decode steps of the window by the attention "
                f"their dispatch span names: {decode_attention(m)}")
            groups = m["engine_metrics"].get("kv_window_groups")
            log(f"[serve] page groups at the window's end: full "
                f"{m['engine_metrics'].get('kv_blocks_used')} of "
                f"{m['engine_metrics'].get('kv_blocks_total')} pages; "
                f"window groups {groups}")
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
