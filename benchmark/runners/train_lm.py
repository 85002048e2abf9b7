"""Runner of a causal-LM training configuration: DataParallelTrainer ->
one train worker that owns the chip -> ``make_causal_lm_trainer``'s
jitted step, fed by ``session.get_dataset_shard`` +
``iter_device_batches``. The train loop below is the benchmark's own (a
user writes one like it); everything it calls is the program's."""

from __future__ import annotations

import os
import time

# The first step's gradient norm against the plain reference
# (benchmark/reference/gpt2_ref.py, float32 at 'highest'), relative: the
# number that decides ``correct``. On the chip at the cell's size (PERF.md
# section 2) the program read at most 1.93e-3 over 9 seeds and the fp8
# control at least 0.936 over 4. The first step's loss is printed beside
# it and not compared: at initialisation it sits at ln(V) whatever the
# arithmetic, so fp8 moves it by only 1.9e-5 to 9.5e-5 where bfloat16
# moves it by up to 7.8e-6, and no limit between those would hold.
GRAD_REL_LIMIT = 2.0e-2


def train_loop(config):
    """Runs inside the train worker."""
    import jax
    import numpy as np

    from benchmark.harness import chips, spans
    from benchmark.reference import gpt2_glue, gpt2_ref
    from benchmark.traffic_kinds import train_epochs
    from ray_tpu.air import session
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.spmd import make_causal_lm_trainer

    rehearse = config["rehearse"]
    traffic = config["traffic"]
    out = {"device": chips.device_report(config["chips"], rehearse),
           "pid": os.getpid()}
    rec = spans.Recorder()
    rec.listen_for_compiles()
    sizes = config["sizes"]
    cfg = gpt2_glue.model_config(config["model"], sizes.get("model_kwargs"))
    spec = MeshSpec()
    mesh = spec.build(jax.devices()[:1])
    trainer = make_causal_lm_trainer(cfg, mesh=mesh, spec=spec)
    # the benchmark's weights from the seed, under the trainer's own
    # optimizer state and shardings
    state = trainer.init(jax.random.PRNGKey(0))
    state["params"] = jax.device_put(
        gpt2_glue.to_flax_tree(gpt2_glue.init_for(cfg, config["seed"])),
        trainer.state_sharding_tree["params"])
    batch_size, seq = sizes["batch_size"], sizes["seq_len"]
    tokens_per_step = batch_size * seq
    feed = train_epochs.epochs(session.get_dataset_shard("tokens"),
                               batch_size, trainer.batch_shardings)

    # warm-up: the first step compiles (or loads); its loss and gradient
    # norm are the numbers compared with the reference
    first_batch = next(feed)
    # the reference's batch comes from the seed, not from the feed; what
    # the feed delivered is compared with it, token for token
    vocab = cfg.vocab_size
    first_ids = train_epochs.make_rows(traffic, config["seed"], vocab,
                                       seq)[:batch_size]
    fed_wrong = int(np.sum(np.asarray(first_batch["input_ids"])
                           != first_ids))
    t_c = time.time()
    state, metrics = trainer.step(state, first_batch)
    first = {k: float(jax.block_until_ready(v)) for k, v in metrics.items()}
    out["first_step_s"] = time.time() - t_c
    for _ in range(int(traffic["warmup_steps"])):
        state, metrics = trainer.step(state, next(feed))
    jax.block_until_ready(metrics["loss"])

    # ---- the window
    trace_dir = config.get("trace_dir")
    trace_from = int(traffic["trace_after_steps"])
    trace_steps = int(traffic["trace_steps"])
    in_flight_max = int(traffic["max_in_flight_steps"])
    pending, step_done, steps = [], [], 0
    tracing, traced = False, False
    t0 = time.time()
    t_end = t0 + float(config["seconds"])
    while time.time() < t_end:
        if trace_dir and not traced and not tracing and steps == trace_from:
            for m in pending:
                jax.block_until_ready(m["loss"])
            pending.clear()
            jax.profiler.start_trace(trace_dir)
            tracing, window_cm = True, jax.profiler.TraceAnnotation(
                "bench.window")
            window_cm.__enter__()
        with rec.span("next(feed)"):
            batch = next(feed)
        with rec.span("step"):
            state, metrics = trainer.step(state, batch)
        steps += 1
        pending.append(metrics)
        while len(pending) >= in_flight_max:
            jax.block_until_ready(pending.pop(0)["loss"])
            step_done.append(time.time())
        if tracing and steps == trace_from + trace_steps:
            for m in pending:
                jax.block_until_ready(m["loss"])
                step_done.append(time.time())
            pending.clear()
            window_cm.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing, traced = False, True
    for m in pending:
        jax.block_until_ready(m["loss"])
        step_done.append(time.time())
    t1 = time.time()
    if tracing:
        window_cm.__exit__(None, None, None)
        jax.profiler.stop_trace()
    last_loss = float(metrics["loss"])
    out.update({
        "t0": t0, "t1": t1, "steps": steps,
        "tokens_per_step": tokens_per_step, "last_loss": last_loss,
        "step_done": step_done,
        "observed": rec.between(t0, t1),
        "compiles_total": len(rec.compiles),
        "compile_seconds_total": sum(c["seconds"] for c in rec.compiles)})

    # ---- after the window: memory, then the reference
    out["memory_peak_bytes"] = chips.memory_peak_bytes()
    out["memory_stats"] = {k: int(v) for k, v in (
        jax.devices()[0].memory_stats() or {}).items()
        if isinstance(v, (int, float))}
    try:
        ma = trainer.step.lower(state, first_batch).compile(
            ).memory_analysis()
        out["step_program_bytes"] = {
            "temp": int(ma.temp_size_in_bytes),
            "argument": int(ma.argument_size_in_bytes),
            "output": int(ma.output_size_in_bytes),
            "alias": int(ma.alias_size_in_bytes)}
    except Exception as e:  # noqa: BLE001 - a reading, not the run
        out["step_program_bytes"] = {"error": repr(e)}
    del state, batch, first_batch
    ref_params = gpt2_glue.init_for(cfg, config["seed"])
    ln_eps = float(config["ln_eps"])
    rows = int(sizes.get("reference_rows_per_call", 2))
    ref_loss, ref_gnorm = gpt2_ref.loss_and_grad_norm(
        ref_params, first_ids, cfg.n_head, rows_per_call=rows,
        ln_eps=ln_eps)
    out["check"] = {"fed_wrong": fed_wrong,
                    "loss": first["loss"], "ref_loss": ref_loss,
                    "grad_norm": first["grad_norm"],
                    "ref_grad_norm": ref_gnorm}
    if config.get("control"):
        c_loss, c_gnorm = gpt2_ref.loss_and_grad_norm(
            ref_params, first_ids, cfg.n_head, quant=gpt2_ref.fp8,
            rows_per_call=rows, ln_eps=ln_eps)
        out["check"]["control_loss"] = c_loss
        out["check"]["control_grad_norm"] = c_gnorm
    session.report(out)


def compare(check, log):
    """Prints each number compared beside its limit; True if all hold."""
    rel_loss = abs(check["loss"] - check["ref_loss"]) / abs(check["ref_loss"])
    rel_g = abs(check["grad_norm"] - check["ref_grad_norm"]) \
        / abs(check["ref_grad_norm"])
    log(f"[correct] first-step gradient norm {check['grad_norm']:.6f} vs "
        f"reference {check['ref_grad_norm']:.6f}: relative difference "
        f"{rel_g:.3e} (limit {GRAD_REL_LIMIT:.1e}); first-step loss "
        f"{check['loss']:.6f} vs {check['ref_loss']:.6f}, relative "
        f"difference {rel_loss:.3e} (not compared)")
    fed_wrong = int(check.get("fed_wrong", 0))
    log(f"[correct] first batch as iter_device_batches fed it vs the rows "
        f"made from the seed: {fed_wrong} differing tokens (limit 0)")
    nums = {"loss_rel": rel_loss, "grad_rel": rel_g, "fed_wrong": fed_wrong}
    if "control_loss" in check:
        nums["control_loss_rel"] = abs(
            check["control_loss"] - check["ref_loss"]) / abs(check["ref_loss"])
        nums["control_grad_rel"] = abs(
            check["control_grad_norm"] - check["ref_grad_norm"]) \
            / abs(check["ref_grad_norm"])
        log(f"[control] fp8 reference in the program's place: loss "
            f"relative difference {nums['control_loss_rel']:.3e}, "
            f"gradient norm {nums['control_grad_rel']:.3e}")
    return rel_g <= GRAD_REL_LIMIT and fed_wrong == 0, nums


def run(ctx):
    """Driver side: never touches a JAX backend."""
    from benchmark.harness import cells
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.data_parallel_trainer import DataParallelTrainer

    cell, log = ctx["cell"], ctx["log"]
    cfg, traffic = cell["config_data"], dict(cell["traffic_data"])
    rehearse = ctx["rehearse"]
    sizes = dict(cfg["train"])
    if rehearse:
        sizes.update(cfg["rehearse"])
        traffic.update(traffic.get("rehearse", {}))
    kind = cells.kind_module(cell)
    vocab = (sizes.get("model_kwargs") or cfg["model"]["kwargs"])[
        "vocab_size"]
    dataset = kind.make_dataset(traffic, ctx["seed"], vocab,
                                sizes["seq_len"])
    config = {"rehearse": rehearse, "traffic": traffic, "sizes": sizes,
              "model": cfg["model"], "seed": ctx["seed"],
              "seconds": ctx["seconds"], "chips": cell["chips"],
              "ln_eps": cfg["ln_eps_as_run"],
              "trace_dir": ctx["trace_dir"] if ctx["trace"] else None,
              "control": ctx.get("control", False)}
    resources = {"CPU": 1} if rehearse else {"TPU": cell["chips"]}
    log(f"[train] fitting: {sizes['batch_size']} x {sizes['seq_len']} "
        f"tokens a step, {traffic['rows']} rows an epoch")
    result = DataParallelTrainer(
        train_loop, train_loop_config=config, datasets={"tokens": dataset},
        scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker=resources)).fit()
    if result.error:
        raise RuntimeError(f"train worker failed: {result.error}")
    m = result.metrics
    window_s = m["t1"] - m["t0"]
    log(f"[train] {m['steps']} steps in {window_s:.3f}s, first step "
        f"{m['first_step_s']:.1f}s, compile requests {m['compiles_total']} "
        f"({m['compile_seconds_total']:.1f}s), last loss "
        f"{m['last_loss']:.4f}")
    slow = sorted(m["observed"]["spans"],
                  key=lambda sp: sp["t0"] - sp["t1"])[:3]
    done = [m["t0"]] + list(m["step_done"])
    gaps = sorted(((b - a, a - m["t0"]) for a, b in zip(done, done[1:])),
                  reverse=True)[:3]
    log("[train] longest host spans (name, at, seconds): "
        + str([(sp["name"], round(sp["t0"] - m["t0"], 3),
                round(sp["t1"] - sp["t0"], 3)) for sp in slow])
        + "; longest waits between finished steps (seconds, at): "
        + str([(round(g, 3), round(at, 3)) for g, at in gaps]))
    ok, nums = compare(m["check"], log)
    tokens = m["steps"] * m["tokens_per_step"]
    peak = m["memory_peak_bytes"]
    prog = m.get("step_program_bytes") or {}
    if "temp" in prog:
        # memory_stats() leaves out the step program's temporaries
        # (PERF.md section 7): the resident bytes plus what the compiler
        # says the step needs is the peak to size by
        resident = m["memory_stats"].get("bytes_in_use", 0)
        peak = max(peak, resident + prog["temp"])
    return {
        "correct": ok, "attempted": m["steps"], "failed": 0,
        "window": (m["t0"], m["t1"]),
        "end_to_end": {
            "train_tokens_per_s": tokens / window_s / cell["chips"]},
        "device": dict(m["device"], memory_peak_bytes=int(peak)),
        "observations": {
            "kind": "train", "spans": m["observed"]["spans"],
            "compiles_in_window": len(m["observed"]["compiles"]),
            "window_s": window_s, "config": cfg, "sizes": sizes},
    }
