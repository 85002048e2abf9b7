"""Runner of the Jamba serving configuration: ``serve_llm.py``'s replica
and driver, with this model's weights and reference in the places where
that file names GPT-2's. What it can share it imports, from
``serve_llm.py`` (the warm-up of every reachable shape, the profiler
hook, the window's measurement), from ``serve_llm_kimi_linear.py`` (a
relative distance, the key a prompt is remembered under, the steps'
medians) and from ``serve_llm_kimi_k2.py`` (the reachable prompt range,
the pick of completed requests, the split of the window's steps); none of
them is edited. Its own: a probe of the Mamba state that every finished
request leaves in its slot, the counters read before the load starts,
what the window's admissions cost, and the comparison that decides
``correct``.

The replica holds ONE copy of the weights: the program's own bfloat16
tree, which the plain reference reads and lifts to float32 a layer at a
time.
"""

from __future__ import annotations

import importlib.util
import sys
import time

# A checkout whose program lacks the model (the parent of the PR that
# added it) cannot run this configuration: say so and leave at once,
# before a cluster is started (a replica whose constructor cannot import
# the model is started again and again: PERF.md, PR 28).
if importlib.util.find_spec("ray_tpu.models.jamba") is None:
    sys.exit("benchmark: this checkout's program has no "
             "ray_tpu.models.jamba; the configuration jamba2_3b cannot run "
             "on it")

from benchmark.runners import serve_llm                       # noqa: E402
from benchmark.runners.serve_llm_kimi_k2 import (             # noqa: E402
    pick_completed, reachable, say_slow_steps)
from benchmark.runners.serve_llm_kimi_linear import (         # noqa: E402
    _prompt_key, _state_err, say_steps)
from ray_tpu.serve.llm import LLMServer                       # noqa: E402

# The limits of `correct`, each between two chip readings (PERF.md
# section 2). The reference is benchmark/reference/jamba_ref.py, float32
# at 'highest', teacher-forced over the whole served sequence.
# [LIMITS-JAMBA] (readings: my chip runs, PR 48)
# A served token's reference logit may lie this far under its row's
# maximum (logits of spread 1.01). Program: a request's largest 0.074 to
# 0.163 over its 300-700 served tokens; fp8 control: 2.08 to 3.18 a
# request (a run's reading, the worst of its four, 2.65 to 3.18).
GAP_LIMIT = 0.6
# The Mamba state a finished request left in its slot, against the
# reference's state after the same tokens, as the norm of the difference
# over the reference's norm (of both states' projections: `_probe`).
# Program: 0.0225 to 0.0308; fp8 control: 0.388 to 0.502 a request.
STATE_ERR_LIMIT = 0.11
# ... and the share of that state's values that bfloat16 cannot hold
# (their low 16 bits are not zero): all but 2^-16 of a float32 state's
# (the program's 0.99995+), none of a state that was kept in bfloat16
# between tokens (the bfloat16-state control's 0.0000), which is what the
# configuration's float32 state rules out and no distance shows: that
# control's state error reads 0.012 to 0.029 and its gap 0.011 to 0.091,
# as the program's own (the bfloat16 products move both more).
STATE_F32_SHARE_LEAST = 0.5
# directions of the channel axis that a state is projected on
PROBE_DIRECTIONS = 8


def _probe(state):
    """Mamba states [n_mamba, N, d_in] (channels minor, as the pool holds
    them) -> (their projection [n_mamba, N, 8] on eight fixed directions
    of the channel axis, float32 at 'highest'; the share of their values
    whose low 16 bits are not zero). 13 KB of an 8.5 MB state: what is
    kept of every request that finishes, some seventeen a second."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    s = state.astype(jnp.float32)
    r = jnp.asarray(np.random.default_rng(48).standard_normal(
        (s.shape[-1], PROBE_DIRECTIONS)), jnp.float32)
    low = jax.lax.bitcast_convert_type(s, jnp.uint32) & 0xFFFF
    return (jnp.einsum("lnc,ck->lnk", s, r,
                       precision=jax.lax.Precision.HIGHEST),
            jnp.mean((low != 0).astype(jnp.float32)))


class BenchJambaServer(serve_llm.BenchLLMServer):
    def __init__(self, model, bench, engine_config):
        import jax

        from benchmark.harness import chips, spans
        from benchmark.reference import jamba_glue as glue
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
        leaves in its state slot is probed before the slot is given back
        (``state_of`` reads the slot's own rows by a dynamic slice, then
        ``_probe``: two small programs a finished request, both warmed
        with the warm-up's own sequences), under the sequence's prompt.
        The probe is dispatched and its copy to the host started, and
        neither is waited for: it reads the slot before any later step
        writes it, and the engine thread goes on
        (``serve_llm_smallthinker.py``, and for its reason)."""
        import jax
        rec, adapter = self._rec, self.adapter
        prefill, decode, release = (adapter.prefill, adapter.decode,
                                    adapter.release)
        probe = self._probe = jax.jit(_probe)
        self._prompt_of, self._probes, self._pending = {}, {}, []

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
                self._settle(keep=32)   # (the older ones: long arrived)
                got, share = probe(adapter.state_of(seq_id)["mamba_state"])
                got.copy_to_host_async()
                share.copy_to_host_async()
                self._pending.append(
                    (key, adapter._state[seq_id]["len"], got, share))
            return release(seq_id)

        adapter.prefill, adapter.decode = traced_prefill, traced_decode
        adapter.release = probing_release

    def _settle(self, keep=0):
        """The probes whose copies were started, as host arrays; all but
        the newest ``keep`` (several requests may end in one step: the
        engine thread does not wait for the one it has just started)."""
        import numpy as np
        while len(self._pending) > keep:
            key, fed, got, share = self._pending.pop(0)
            self._probes[key] = (fed, (np.asarray(got), float(share)))

    def __bench_counters__(self):
        """Called before each run's load starts: drops the probes kept
        so far (the warm-up's, an earlier seed's) and returns the
        adapter's counters now (the warm-up's admissions compile the
        hand-over, so what a window reads is their growth from here)."""
        self._settle()
        self._probes.clear()
        return {k: v for k, v in self.adapter.counters().items()
                if isinstance(v, (int, float))}

    def __bench_settle__(self):
        """Once every program is compiled: what the process holds by now
        is collected once and put aside from the collector (``gc.freeze``,
        as ``serve_llm_smallthinker.py`` does and for its reason)."""
        import gc
        gc.collect()
        gc.freeze()
        return {"frozen": gc.get_freeze_count()}

    def __bench_check__(self, samples, pad_to, _unused=None, control=False):
        """Teacher-force sampled served requests through the plain
        reference, here because this process holds the chip."""
        import jax.numpy as jnp
        import numpy as np

        from benchmark.reference import jamba_ref as ref
        sizes = ref.sizes_of(self.adapter.cfg)
        self._settle()
        rows = []
        for s in samples:
            r = ref.served_token_gaps(
                self.adapter.params["params"], s["prompt"], s["served"],
                sizes, pad_to, controls=ref.CONTROLS if control else ())

            def probed(state):      # the reference's is [.., d_in, N]
                got, share = self._probe(jnp.swapaxes(state, 1, 2))
                return np.asarray(got), float(share)
            want, _ = probed(r["state"])
            fed, (got, f32_share) = self._probes.get(
                _prompt_key(s["prompt"]), (-1, (np.inf * want, 0.0)))
            row = {"index": s["index"], "n": len(s["served"]),
                   "n_prompt": len(s["prompt"]),
                   "max_gap": float(np.max(r["gaps"])),
                   "argmax_equal": r["argmax_equal"],
                   "logit_std": r["logit_std"],
                   # the slot took in all but the last served token
                   "state_tokens_ok":
                       fed == len(s["prompt"]) + len(s["served"]) - 1,
                   "state_err": _state_err(got, want),
                   "state_f32_share": float(f32_share)}
            for name, *_ in (ref.CONTROLS if control else ()):
                low, low_share = probed(r[f"control_{name}_state"])
                row[f"control_{name}"] = {
                    "max_gap": float(np.max(r[f"control_{name}_gaps"])),
                    "state_err": _state_err(low, want),
                    "state_f32_share": low_share}
            rows.append(row)
        return rows

    def __bench_reseed__(self, seed):
        """New weights of the same shapes (the builder's many-seed runs
        in one set-up): the old go first, two sets do not fit."""
        from benchmark.reference import jamba_glue as glue
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
        log(f"[correct] request {r['index']}: prompt {r['n_prompt']}, "
            f"{r['n']} served tokens, {r['argmax_equal']} equal the "
            f"reference argmax (logit std {r['logit_std']:.3f}), state fed "
            f"the right tokens: {r['state_tokens_ok']}; {say(r)}")
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
    log(f"[correct] verdict: {ok}")
    return ok, nums


def say_admissions(m, before, log):
    """What the state slots' hand-overs cost since the warm-up, how many
    sequences the window's prefill steps admitted and in which programs,
    and which paths the decode steps' dispatch spans name."""
    from benchmark.harness import program_spans as ps
    em = m["engine_metrics"]
    n = em.get("state_admits_total", 0) - before.get("state_admits_total", 0)
    s = em.get("state_admit_seconds_total", 0.0) \
        - before.get("state_admit_seconds_total", 0.0)
    steps = ps.steps_between(em.get("step_log"), m["res"]["t0"],
                             m["res"]["t1"]) or ()
    said, programs, admitted, prefills = {}, {}, 0, 0
    for st in steps:
        groups = ps.named(st, "llm.step.prefill")
        prefills += bool(groups)
        admitted += sum(g.get("attrs", {}).get("n", 0) for g in groups)
        for g in groups:
            for sp in ps.named(g, "runner.dispatch"):
                a = sp.get("attrs", {})
                key = f"({a.get('B')}, {a.get('S')})"
                programs[key] = programs.get(key, 0) + 1
        for d in ps.named(st, "llm.step.decode"):
            for sp in ps.named(d, "runner.dispatch"):
                a = sp.get("attrs", {})
                key = (f"{a.get('recurrence')}+{a.get('attention')} "
                       f"b{a.get('B')}")
                said[key] = said.get(key, 0) + 1
    log(f"[serve] state slots since the warm-up: {n} sequences admitted, "
        f"{1e3 * s / max(n, 1):.3f} ms of the host each inside "
        f"runner.state.admit; of the window's {len(steps)} engine steps "
        f"{prefills} carried a prefill and admitted {admitted} sequences "
        f"({admitted / m['window_s']:.1f} a second, "
        f"{admitted / max(prefills, 1):.2f} a prefill step); prefill "
        f"programs of the window: {dict(sorted(programs.items()))}; decode "
        f"steps by the paths and bucket of their dispatch span: "
        f"{dict(sorted(said.items()))}; recurrence_kernel_steps_total "
        f"{em.get('recurrence_kernel_steps_total')}")


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
        **options)(BenchJambaServer)
    t_dep = time.time()
    log("[serve] deploying the replica (weights from the seed, "
        f"{engine['num_blocks']} x {engine['block_size']}-token K and V "
        f"pages for the attention layers, {engine['max_running']} state "
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
