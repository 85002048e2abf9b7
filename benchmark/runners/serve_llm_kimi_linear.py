"""Runner of the Kimi-Linear serving configuration: ``serve_llm.py``'s
replica and driver, with this model's weights and reference in the places
where that file names GPT-2's. What it can share it imports (the
warm-up of every reachable shape, the profiler hook, the window's
measurement, the sampling of requests to check); ``serve_llm.py`` itself
is not edited. Its own: the spans round the adapter's calls (this
adapter's calls take a keyword), a probe of the state that every
finished request leaves in its slot, and the comparison that decides
``correct``.

The replica holds ONE copy of the weights: the program's own bfloat16
tree, which the plain reference reads and lifts to float32 a layer at a
time (two copies of 7.5 GB do not fit beside the state and the pool).
"""

from __future__ import annotations

import importlib.util
import sys
import time

from benchmark.runners import serve_llm
from ray_tpu.serve.llm import LLMServer

# A checkout whose program lacks the model (the parent of the PR that
# added it) cannot run this configuration: say so and leave at once,
# before a cluster is started. (A replica whose constructor cannot import
# the model is started again and again by the serve controller; the run
# would hang until its time limit: my chip run, PR 28.)
if importlib.util.find_spec("ray_tpu.models.kimi_linear") is None:
    sys.exit("benchmark: this checkout's program has no "
             "ray_tpu.models.kimi_linear; the configuration "
             "kimi_linear_48b_a3b cannot run on it")

# The limits of `correct`, each between two chip readings (PERF.md
# section 2). The reference is benchmark/reference/kimi_linear_ref.py,
# float32 at 'highest', teacher-forced over the whole served sequence.
# [LIMITS-KIMI]
# A served token's reference logit may lie this far under its row's
# maximum (logits of spread 0.96): the program's largest 0.670 over 18
# runs, the fp8 control's smallest per-run reading 0.879 over 12.
GAP_LIMIT = 0.78
# The KDA state a finished request left in its slot, against the
# reference's state after the same tokens, as the norm of the difference
# over the reference's norm (of both states' projections: `_probe`): the
# program's largest 0.0607 over 20 requests of 5 runs, the fp8 control's
# smallest 0.186 over 8 requests (0.186-0.202: it hardly moves).
STATE_ERR_LIMIT = 0.12
# ... and the share of that state's values that bfloat16 cannot hold (their
# low 16 bits are not zero): all but 2^-16 of a float32 state's, none of
# a state that was kept in bfloat16 between tokens, which is what the
# configuration's float32 state rules out and no distance shows: the
# bfloat16-state control's logits and state lie as close to the float32
# reference as the program's own (state error 0.017-0.036 against the
# program's 0.016-0.061: the bfloat16 products move both more).
STATE_F32_SHARE_LEAST = 0.5


def _probe(state):
    """KDA states [n_kda, H, dk, dv] -> (their projection [n_kda, H, dv]
    on one fixed direction of the key axis, float32 at 'highest'; the
    share of their values whose low 16 bits are not zero). 98 KB of a
    12.6 MB state: what is kept of every request that finishes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    s = state.astype(jnp.float32)
    r = jnp.asarray(np.random.default_rng(28).standard_normal(
        s.shape[-2]), jnp.float32)
    low = jax.lax.bitcast_convert_type(s, jnp.uint32) & 0xFFFF
    return (jnp.einsum("lhkv,k->lhv", s, r,
                       precision=jax.lax.Precision.HIGHEST),
            jnp.mean((low != 0).astype(jnp.float32)))


def _state_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _prompt_key(prompt):
    return len(prompt), tuple(int(t) for t in prompt[:32])


class BenchKimiLinearServer(serve_llm.BenchLLMServer):
    def __init__(self, model, bench, engine_config):
        import jax

        from benchmark.harness import chips, spans
        from benchmark.reference import kimi_linear_glue as glue
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
        """``serve_llm``'s spans round the adapter's calls (here with the
        calls' own arguments passed on: the engine asks this adapter for
        tokens in place of logits), and: what every finishing sequence
        leaves in its state slot is probed before the slot is given back
        (`_probe`: two small programs a finished request, both warmed
        with the warm-up's own sequences), under the sequence's prompt."""
        import jax
        rec, adapter = self._rec, self.adapter
        prefill, decode, release = (adapter.prefill, adapter.decode,
                                    adapter.release)
        probe = self._probe = jax.jit(_probe)
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
            if key is not None and seq_id in adapter._state:
                self._probes[key] = (
                    adapter._state[seq_id]["len"],
                    probe(adapter.state_of(seq_id)["kda_state"]))
            return release(seq_id)

        adapter.prefill, adapter.decode = traced_prefill, traced_decode
        adapter.release = probing_release

    def __bench_check__(self, samples, pad_to, _unused=None, control=False):
        """Teacher-force sampled served requests through the plain
        reference, here because this process holds the chip."""
        import numpy as np

        from benchmark.reference import kimi_linear_ref as ref
        sizes = ref.sizes_of(self.adapter.cfg)
        rows = []
        for s in samples:
            r = ref.served_token_gaps(
                self.adapter.params["params"], s["prompt"], s["served"],
                sizes, pad_to, controls=ref.CONTROLS if control else ())
            want, _ = self._probe(r["state"])
            fed, (got, f32_share) = self._probes.get(
                _prompt_key(s["prompt"]), (-1, (np.inf * want, 0.0)))
            row = {"index": s["index"], "n": len(s["served"]),
                   "max_gap": float(np.max(r["gaps"])),
                   "argmax_equal": r["argmax_equal"],
                   "logit_std": r["logit_std"],
                   # the slot took in all but the last served token
                   "state_tokens_ok":
                       fed == len(s["prompt"]) + len(s["served"]) - 1,
                   "state_err": _state_err(got, want),
                   "state_f32_share": float(f32_share)}
            for name, *_ in (ref.CONTROLS if control else ()):
                low, low_share = self._probe(r[f"control_{name}_state"])
                row[f"control_{name}"] = {
                    "max_gap": float(np.max(r[f"control_{name}_gaps"])),
                    "state_err": _state_err(low, want),
                    "state_f32_share": float(low_share)}
            rows.append(row)
        return rows

    def __bench_reseed__(self, seed):
        """New weights of the same shapes (the builder's many-seed runs
        in one set-up): the old go first, two sets do not fit."""
        from benchmark.reference import kimi_linear_glue as glue
        self.adapter.params = None
        self.adapter.params = glue.init_for(self.adapter.cfg, seed)
        return True


def within_limits(r) -> bool:
    """One request's numbers (or a control's in their place)."""
    return (r["max_gap"] <= GAP_LIMIT and r["state_err"] <= STATE_ERR_LIMIT
            and r["state_f32_share"] >= STATE_F32_SHARE_LEAST)


def compare(rows, log):
    """`correct`: every sampled request within every limit, and its slot
    fed the tokens it should have been. Each number is said beside its
    limit; ``nums`` holds the worst of each, and for each control whether
    it would have passed in the program's place."""
    controls = sorted({k for r in rows for k in r
                       if k.startswith("control_")})

    def say(r):
        return (f"largest gap under the row maximum {r['max_gap']:.4f} "
                f"(limit {GAP_LIMIT}), state error {r['state_err']:.5f} "
                f"(limit {STATE_ERR_LIMIT}), float32 share of the state "
                f"{r['state_f32_share']:.4f} (least "
                f"{STATE_F32_SHARE_LEAST})")
    for r in rows:
        log(f"[correct] request {r['index']}: {r['n']} served tokens, "
            f"{r['argmax_equal']} equal the reference argmax (logit std "
            f"{r['logit_std']:.3f}), state fed the right tokens: "
            f"{r['state_tokens_ok']}; {say(r)}")
        for k in controls:
            log(f"[correct]   {k[8:]} control in its place: {say(r[k])}")
    nums = {}
    for name, worst in (("max_gap", max), ("state_err", max),
                        ("state_f32_share", min)):
        nums[name] = worst((r[name] for r in rows), default=None)
        for k in controls:
            nums[f"{k}_{name}"] = worst(r[k][name] for r in rows)
    for k in controls:
        nums[f"{k}_passes"] = all(within_limits(r[k]) for r in rows)
    ok = bool(rows) and all(
        within_limits(r) and r["state_tokens_ok"] for r in rows)
    return ok, nums


def say_steps(m, log):
    """The medians of the window's step spans, in every run and not only
    the traced one: which span's length differs between two runs of one
    program is read from these lines."""
    import types

    from benchmark.harness import program_spans, stats
    obs = types.SimpleNamespace(
        engine_metrics=m["engine_metrics"], t0=m["res"]["t0"],
        t1=m["res"]["t1"], trace_window_host=m["trace"])
    program_spans.describe_steps(obs)
    steps = program_spans.window_steps(obs) or []
    log(f"[serve] llm.step less runner.* (the engine's own), median: "
        f"{stats.median([program_spans.self_ms(s) for s in steps]) or 0:.3f}"
        f" ms over {len(steps)} steps")


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
    bench = {"chips": cell["chips"], "rehearse": rehearse,
             "model": cfg["model"], "model_kwargs": model_kwargs,
             "seed": ctx["seed"],
             "warm_prompt": traffic["prompt_len"]["min"]}
    options = ({} if rehearse
               else {"ray_actor_options": {"num_tpus": cell["chips"]}})
    dep = serve.deployment(
        name="bench_llm", num_replicas=1,
        max_concurrent_queries=int(cfg["serve"]["max_concurrent_queries"]),
        **options)(BenchKimiLinearServer)
    t_dep = time.time()
    log("[serve] deploying the replica (weights from the seed, "
        f"{engine['num_blocks']} x {engine['block_size']}-token latent "
        f"pages, {engine['max_running']} state slots)")
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
        serve_llm.warm_up(handle, engine, traffic, log)
        runs = []
        for i, seed in enumerate(ctx.get("seeds") or [ctx["seed"]]):
            if i:
                serve_llm._call(handle, "__bench_reseed__", seed, log=log)
            trace_dir = ctx["trace_dir"] if ctx["trace"] and not i else None
            m = serve_llm.measure(handle, kind, traffic, seed,
                                  ctx["seconds"], vocab, trace_dir, log)
            if not trace_dir:       # a traced run's readers say them
                say_steps(m, log)
            samples = serve_llm.pick_samples(
                m["res"]["records"], m["res"]["t0"], m["res"]["t1"], seed,
                int(traffic["check_requests"]), vocab)
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
