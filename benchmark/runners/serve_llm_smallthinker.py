"""Runner of the SmallThinker serving configuration: ``serve_llm.py``'s
replica and driver, with this model's weights and reference in the places
where that file names GPT-2's. What it can share it imports, from
``serve_llm.py`` (the warm-up, the profiler hook, the window's
measurement), from ``serve_llm_kimi_linear.py`` (the steps' medians, the
key a prompt is remembered under, a relative distance), from
``serve_llm_kimi_k2.py`` (the split of the window's steps, the reachable
prompt range) and from ``serve_llm_laguna.py`` (which attention the
decode steps' dispatch spans name); none of them is edited. Its own: a
probe of the K and V rows that every finished request left in BOTH page
groups (the last rows written, and in the window group the first rows of
what a window layer still reads, each found by the ring rule in the
pages the sequence holds, a whole ring or fewer), a pick of checked
requests that holds one that wrapped its ring and one that did not, what
the window's admissions waited for, and the comparison that decides
``correct``.

The replica holds ONE copy of the weights: the program's own bfloat16
tree, which the plain reference reads and lifts to float32 an expert at
a time (two copies of 7.9 GB do not fit beside the pools).
"""

from __future__ import annotations

import importlib.util
import sys
import time

# A checkout whose program lacks the model (the parent of the PR that
# added it) cannot run this configuration: say so and leave at once,
# before a cluster is started (a replica whose constructor cannot import
# the model is started again and again: PERF.md, PR 28).
if importlib.util.find_spec("ray_tpu.models.smallthinker") is None:
    sys.exit("benchmark: this checkout's program has no "
             "ray_tpu.models.smallthinker; the configuration "
             "smallthinker_21b_a3b cannot run on it")

from benchmark.runners import serve_llm                       # noqa: E402
from benchmark.runners.serve_llm_kimi_k2 import (             # noqa: E402
    reachable, say_slow_steps)
from benchmark.runners.serve_llm_kimi_linear import (         # noqa: E402
    _prompt_key, _state_err as _rel_err, say_steps)
from benchmark.runners.serve_llm_laguna import decode_attention  # noqa: E402
from ray_tpu.serve.llm import LLMServer                       # noqa: E402

# The limits of `correct` (PERF.md section 2; all readings: my chip runs,
# PR 43, 76 requests of 19 runs). The reference is
# benchmark/reference/smallthinker_ref.py, float32 at 'highest',
# teacher-forced over the whole served sequence.
# [LIMITS-SMALLTHINKER]
# A served token's reference logit may lie this far under its row's
# maximum (logits of spread 1.0). A guard against gross faults, not what
# tells the precision control: the program's largest reading is 0.306
# (a request's maximum over ~900 tokens, median 0.067; of 17,533 served
# tokens 13 lie over 0.1, one over 0.2, none over 0.25) and the fp8
# control's run readings are 0.344-0.626, so
# no limit lies between them with room; window layers that see the whole
# context read 1.06-1.40 where the sampled request ran far past its
# window.
GAP_LIMIT = 1.2
# The K (rotated on a window layer, as computed on a full one) and V rows
# a finished request left in the pools against the rows the reference
# would cache at those positions, norm of the difference over the
# reference's norm: the two full layers' last 256 positions
# (``full_err``), and the six window layers' last 256 and the first 256
# of what they still read, each found in the pages the sequence holds by
# the ring rule (``ring_err``: a ring indexed wrongly, a row overwritten
# too early, a rotation at a wrong position or rows of another sequence
# read ~1.4). Program: full_err at most 0.0276, ring_err at most 0.0300
# (long contexts read 0.02-0.03, short ones 0.011-0.016); fp8 control, a
# run's reading: full_err at least 0.0889 (single requests 0.0765),
# ring_err at least 0.123 (0.1066): it fails this limit in every run.
KV_ERR_LIMIT = 0.05
# thresholds at which a request's gaps are counted, for the tail's shape
GAP_TAIL = (0.05, 0.1, 0.15, 0.2, 0.25, 0.3)


class BenchSmallThinkerServer(serve_llm.BenchLLMServer):
    def __init__(self, model, bench, engine_config):
        import jax

        from benchmark.harness import chips, spans
        from benchmark.reference import smallthinker_glue as glue
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
        left in both page groups at the probed positions is gathered
        before its pages are given back (``probe``: one small program and
        7.3 MB a finished request, warmed with the warm-up's own
        sequences), under the sequence's prompt. The program is
        dispatched and its copy to the host started, and neither is
        waited for: it runs before any later step writes those pages, and
        the engine thread goes on to the release (a copy waited for where
        it was made kept the chip idle ~27 ms a finished request; my chip
        runs, PR 43). ``_settle`` takes the arrived copies in."""
        import jax
        import numpy as np

        from benchmark.reference.smallthinker_ref import probe_positions
        rec, adapter = self._rec, self.adapter
        prefill, decode, release = (adapter.prefill, adapter.decode,
                                    adapter.release)
        window = adapter.cfg.sliding_window_size

        def rows_at(pool, page, slot):
            # [L, n, C]. Every index an array, the layers' too: the
            # gather then reads single rows where the pool lies. With a
            # slice over the layers (``pool[:, page, slot]``) XLA lays
            # the WHOLE pool out anew first, layers innermost: 2.4 GB of
            # temporaries and ~24 ms of the chip a finished request
            # (`%copy bf16[6,18433,16,512]` 0.183 s of a traced 4 s; my
            # chip runs, PR 43)
            layers = jax.numpy.arange(pool.shape[0])[:, None]
            return pool[layers, page[None], slot[None]]

        def probe(k_full, v_full, k_win, v_win, page, slot, ring_page,
                  ring_slot):
            full = jax.numpy.stack(
                [rows_at(k_full, page, slot), rows_at(v_full, page, slot)],
                axis=1)
            rows = jax.numpy.stack(
                [rows_at(k_win, ring_page, ring_slot),
                 rows_at(v_win, ring_page, ring_slot)], axis=1)
            return full, rows
        probe = jax.jit(probe)
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
            st = adapter._state.get(seq_id)
            if key is not None and st is not None:
                bs, fed = adapter.cache.block_size, st["len"]
                tail, both = probe_positions(fed, window)
                # a position's page: the full group's table by position,
                # the window group's by the ring rule over the whole
                # ring's width (a short ring holds fewer pages and never
                # wraps: the rule names a page it holds)
                held = np.asarray(st["rings"][window], np.int32)
                ring = adapter._rings[window]
                # (clipped: the warm-up decodes its sequences past their
                # budgets, into the null page)
                table = np.asarray(st["table"], np.int32)
                page = table[np.minimum(tail // bs, len(table) - 1)]
                ring_page = held[np.minimum((both // bs) % ring,
                                            len(held) - 1)]
                a = adapter._arrays
                self._settle()      # (the earlier ones: long arrived)
                with adapter._lock:
                    full, rows = probe(
                        a["k_full"], a["v_full"], a["k_window"],
                        a["v_window"], page, (tail % bs).astype(np.int32),
                        ring_page, (both % bs).astype(np.int32))
                full.copy_to_host_async()
                rows.copy_to_host_async()
                self._pending.append((key, fed, len(held), full, rows))
            return release(seq_id)

        adapter.prefill, adapter.decode = traced_prefill, traced_decode
        adapter.release = probing_release

    def _settle(self):
        """The probes whose copies were started, as host arrays (their
        device buffers go)."""
        import numpy as np
        for key, fed, held, full, rows in self._pending:
            self._probes[key] = (fed, held, np.asarray(full),
                                 np.asarray(rows))
        self._pending.clear()

    def __bench_settle__(self):
        """Once every program is compiled: what the process holds by now
        (the programs' traces and executables, the engine, the warm-up's
        records) is collected once and put aside from the collector, as a
        server does when it has started (``gc.freeze``). A collection of
        the oldest generation walked all of it, 0.44 s at a time and one
        to three times a window (my chip runs, PR 43); after this it
        walks what the window itself made."""
        import gc
        gc.collect()
        gc.freeze()
        return {"frozen": gc.get_freeze_count()}

    def __bench_check__(self, samples, pad_to, _unused=None, control=False):
        """Teacher-force sampled served requests through the plain
        reference, here because this process holds the chip."""
        import numpy as np

        from benchmark.reference import smallthinker_ref as ref
        sizes = ref.sizes_of(self.adapter.cfg)
        window = self.adapter.cfg.sliding_window_size
        ring = self.adapter._rings[window]
        self._settle()
        rows = []
        for s in samples:
            r = ref.served_token_gaps(
                self.adapter.params["params"], s["prompt"], s["served"],
                sizes, pad_to, controls=ref.CONTROLS if control else ())
            fed, held, full, win = self._probes.get(
                _prompt_key(s["prompt"]), (-1, 0, None, None))
            row = {"index": s["index"], "n": len(s["served"]),
                   "n_prompt": len(s["prompt"]),
                   "max_gap": float(np.max(r["gaps"])),
                   # the tail the maximum is drawn from (recorded, not
                   # judged): served tokens over each threshold
                   "gaps_over": {t: int(np.sum(r["gaps"] > t))
                                 for t in GAP_TAIL},
                   "argmax_equal": r["argmax_equal"],
                   "logit_std": r["logit_std"],
                   # the pools took in the prompt and all but the last
                   # served token
                   "cache_tokens_ok": fed == r["fed"],
                   # the sequence ran past its window: its ring wrapped
                   "wrapped": r["fed"] > window,
                   "ring_pages_held": held, "ring_pages": ring,
                   "full_err": float("inf") if full is None else
                   _rel_err(full.astype(np.float32), r["full"]),
                   "ring_err": float("inf") if win is None else
                   _rel_err(win.astype(np.float32), r["window"])}
            if full is not None:    # by layer, shallowest first
                row["full_err_by_layer"] = [
                    _rel_err(full.astype(np.float32)[i], r["full"][i])
                    for i in range(len(full))]
                row["ring_err_by_layer"] = [
                    _rel_err(win.astype(np.float32)[i], r["window"][i])
                    for i in range(len(win))]
            for name in ref.CONTROLS if control else ():
                row[f"control_{name}"] = {
                    "max_gap": float(np.max(r[f"control_{name}_gaps"])),
                    "full_err": _rel_err(r[f"control_{name}_full"],
                                         r["full"]),
                    "ring_err": _rel_err(r[f"control_{name}_window"],
                                         r["window"])}
            rows.append(row)
        return rows

    def __bench_reseed__(self, seed):
        """New weights of the same shapes (the builder's many-seed runs
        in one set-up): the old go first, two sets do not fit."""
        from benchmark.reference import smallthinker_glue as glue
        self.adapter.params = None
        self._settle()
        self._probes.clear()
        self.adapter.params = glue.init_for(self.adapter.cfg, seed)
        return True


def within_limits(r) -> bool:
    """One request's numbers (or a control's in their place)."""
    return (r["max_gap"] <= GAP_LIMIT and r["full_err"] <= KV_ERR_LIMIT
            and r["ring_err"] <= KV_ERR_LIMIT)


def compare(rows, log):
    """`correct`: every sampled request within every limit, both page
    groups fed the tokens they should have been, and among the sampled a
    request that wrapped its ring and one that did not. Each number is
    said beside its limit; ``nums`` holds the worst of each, and for each
    control whether it would have passed in the program's place."""
    controls = sorted({k for r in rows for k in r
                       if k.startswith("control_")})

    def say(r):
        return (f"largest gap under the row maximum {r['max_gap']:.4f} "
                f"(limit {GAP_LIMIT}), cached K and V rows' error: full "
                f"layers {r['full_err']:.5f}, window layers' ring "
                f"{r['ring_err']:.5f} (limit {KV_ERR_LIMIT})")
    for r in rows:
        log(f"[correct] request {r['index']}: prompt {r['n_prompt']}, "
            f"{r['n']} served tokens, {r['argmax_equal']} equal the "
            f"reference argmax (logit std {r['logit_std']:.3f}), pools fed "
            f"the right tokens: {r['cache_tokens_ok']}, wrapped its ring: "
            f"{r['wrapped']} ({r['ring_pages_held']} of {r['ring_pages']} "
            f"ring pages held); {say(r)}; tokens over "
            f"{r.get('gaps_over')}; by layer: full "
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
    nums["wrapped"] = sum(r["wrapped"] for r in rows)
    nums["not_wrapped"] = sum(not r["wrapped"] for r in rows)
    ok = bool(rows) and nums["wrapped"] > 0 and nums["not_wrapped"] > 0 \
        and all(within_limits(r) and r["cache_tokens_ok"] for r in rows)
    log(f"[correct] verdict: {ok} ({nums['wrapped']} of the {len(rows)} "
        f"requests wrapped their ring, {nums['not_wrapped']} did not)")
    return ok, nums


def pick_checked(records, seed, n, vocab, window):
    """``n`` of the run's completed requests, by the seed, any request
    that ran to its end whenever it fell due (``serve_llm_kimi_k2.
    pick_completed``'s rule), with at least one that wrapped its ring
    (prompt + served tokens past the window) and one that did not where
    the run completed such: the first of each kind in the seed's order,
    then the order's next."""
    import numpy as np

    from benchmark.harness import loadgen
    done = [r for r in records if r["done"] is not None
            and r["error"] is None and r["tokens"]]
    rng = np.random.default_rng([int(seed), 13])
    order = [done[int(i)] for i in rng.permutation(len(done))]

    def wrapped(r):
        return r["n_prompt"] + len(r["tokens"]) - 1 > window
    picks = [next((r for r in order if wrapped(r) == kind), None)
             for kind in (True, False)]
    picks = [r for r in picks if r is not None]
    picks += [r for r in order if not any(r is p for p in picks)]
    return [{"index": r["index"], "served": r["tokens"],
             "prompt": loadgen.prompt_tokens(seed, r["index"],
                                             r["n_prompt"], vocab)}
            for r in picks[:n]]


def say_page_groups(m, log):
    """What the window's admissions waited for and what the running
    sequences held of the window group (recorded, not acted on)."""
    from benchmark.harness import program_spans as ps, smallthinker_views
    em = m["engine_metrics"]
    held, whole = smallthinker_views.ring_pages(ps.steps_between(
        em.get("step_log"), m["res"]["t0"], m["res"]["t1"]))
    log(f"[serve] page groups at the window's end: full "
        f"{em.get('kv_blocks_used')} of {em.get('kv_blocks_total')} pages; "
        f"window groups {em.get('kv_window_groups')}; admissions that "
        f"waited for pages since the replica started, by the group that "
        f"was short: {em.get('admissions_waited_total')}; over the "
        f"window's decode steps the running sequences held {held} window "
        f"pages where whole rings would be {whole}"
        + (f" ({100.0 * held / whole:.1f}%)" if whole else ""))


def say_slow(m, log):
    """The engine's own records of the steps of a second or more that
    touch the window, in every run and not only the traced one: what a
    window that reads low waited for."""
    t0, t1 = m["res"]["t0"], m["res"]["t1"]
    for r in m["engine_metrics"].get("slow_steps") or ():
        if r["t1"] > t0 and r["t0"] < t1:
            log(f"[serve] slow step {r['t0'] - t0:.1f}s into the window: "
                f"{r['t1'] - r['t0']:.3f} s, verdict {r.get('verdict')!r} "
                f"({str(r.get('why'))[:300]})")


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
    kwargs = model_kwargs or cfg["model"]["kwargs"]
    vocab, window = kwargs["vocab_size"], kwargs["sliding_window_size"]
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
        **options)(BenchSmallThinkerServer)
    t_dep = time.time()
    log("[serve] deploying the replica (weights from the seed, "
        f"{engine['num_blocks']} x {engine['block_size']}-token K and V "
        f"pages for the full layers, {engine['window_blocks']} for the "
        f"window layers (rings taken by need), {engine['max_running']} "
        "decode slots)")
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
            trace_dir = ctx["trace_dir"] if ctx["trace"] and not i else None
            m = serve_llm.measure(handle, kind, traffic, seed,
                                  ctx["seconds"], vocab, trace_dir, log)
            if not trace_dir:       # a traced run's readers say them
                say_steps(m, log)
            say_slow_steps(m, log)
            say_slow(m, log)
            log(f"[serve] decode steps of the window by the attention "
                f"their dispatch span names: {decode_attention(m)}")
            say_page_groups(m, log)
            samples = pick_checked(
                m["res"]["records"], seed, int(traffic["check_requests"]),
                vocab, window)
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
