#!/usr/bin/env python3
"""Chip smoke: the train and serve paths, once, on the TPU, through the
entry points a user calls. The quickest proof that the system still
starts on the chip. It measures nothing: every time it prints is a smoke
timing, not a benchmark.

    python chip_smoke.py             one chip: train, serve, checks
    python chip_smoke.py --chips 4   four chips: isolation + the dp2 x tp2
                                     step against a one-device run, and
                                     no other phase

The last line of stdout is ``{"ok": true, "device": {...}}`` with the
device as the TPU worker saw it. Any failed check, any phase on a
non-TPU device, or no chip at all: non-zero exit and no such line.

Process layout: a chip belongs to one process at a time. This driver
never initialises a jax backend (asserted at the end); every phase runs
in a worker process that owns the chip and must have exited, the chip's
device files closed, before the next phase starts (``wait_chip_free``
looks, it does not sleep and hope).

``--rehearse`` runs the same control flow at tiny sizes on the CPU
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for ``--chips
4``). A rehearsal never prints the ok line and exits 3 when it passes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SEED = 20260926
LOSS_TOL = 2e-2      # |flash - reference| first-step loss, bf16 activations
MESH_TOL = 3e-2      # |dp2 x tp2 - one device| loss per step, bf16
LOGIT_TOL = 0.3      # served token's reference logit vs the row maximum
PAGED_TOL = 4e-2     # paged kernel vs gather reference; one bf16 ulp of
                     # an output in [2, 4) is 1.6e-2


class SmokeFailure(Exception):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def log(msg):
    print(msg, flush=True)


def sizes(rehearse: bool):
    """Real sizes are published widths; rehearsal sizes only prove the
    control flow."""
    if rehearse:
        return dict(batch=2, seq=64, steps=3, img=32, img_batch=8,
                    num_blocks=256, prompts=[8, 33, 20, 40, 17, 64, 50, 12],
                    new_tokens=4, ref_seq=128)
    return dict(batch=16, seq=1024, steps=5, img=224, img_batch=256,
                num_blocks=4096,
                prompts=[32, 128, 100, 200, 256, 384, 512, 64],
                new_tokens=32, ref_seq=640)


def gpt2_config(rehearse: bool):
    from ray_tpu.models.gpt2 import GPT2Config
    if not rehearse:
        return GPT2Config.small()   # 12 L, 768, 12 heads, V 50257, bf16
    import jax.numpy as jnp
    return GPT2Config(vocab_size=512, n_positions=128, n_embd=128,
                      n_layer=2, n_head=4, dtype=jnp.float32)


# ------------------------------------------------------------ worker side
# Everything below this line down to "driver side" runs inside a worker
# process that owns the chip.

def _chip_files(pid="self"):
    """The chip device files a process holds open."""
    held = set()
    try:
        fds = os.listdir(f"/proc/{pid}/fd")
    except OSError:
        return []
    for fd in fds:
        try:
            path = os.readlink(f"/proc/{pid}/fd/{fd}")
        except OSError:
            continue
        d, name = os.path.split(path)
        if (d == "/dev/vfio" and name.isdigit()) or (
                d == "/dev" and name.startswith("accel")):
            held.add(path)
    return sorted(held)


def _device_report(rehearse):
    import jax
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise RuntimeError(
            f"worker pid {os.getpid()} runs on {devs[0].platform!r}, "
            "not on a TPU")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "pid": os.getpid(),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "chip_files": _chip_files(),
            "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _cache_hits():
    """Counts jax's persistent-cache hits in this process from here on."""
    import jax
    hits = [0]

    def listener(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits[0] += 1
    jax.monitoring.register_event_listener(listener)
    return hits


def _platforms(tree):
    import jax
    return sorted({d.platform for leaf in jax.tree_util.tree_leaves(tree)
                   for d in leaf.devices()})


def _compile_gpt2_step(cfg, mesh, spec, make_batch):
    """(state, batch, compiled step, compile seconds, cache hits)."""
    import jax
    from ray_tpu.train.spmd import make_causal_lm_trainer
    hits = _cache_hits()
    trainer = make_causal_lm_trainer(cfg, mesh=mesh, spec=spec)
    state = trainer.init(jax.random.PRNGKey(SEED))
    batch = make_batch(trainer)
    before = hits[0]
    t0 = time.perf_counter()
    step = trainer.step.lower(state, batch).compile()
    return state, batch, step, time.perf_counter() - t0, hits[0] - before


def _run_steps(step, state, batch, n):
    import jax
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        secs.append(time.perf_counter() - t0)
    return state, losses, secs


def _shard_batch(shard, trainer, batch_size):
    return next(iter(shard.iter_device_batches(
        batch_size=batch_size, sharding=trainer.batch_shardings,
        drop_last=True, pad_to_batch=False)))


def train_worker(config):
    """Train phase, inside the framework's train worker: GPT-2 small then
    ResNet-50, a few steps each on one repeated batch."""
    import dataclasses
    import gc

    import jax
    import jax.numpy as jnp

    from ray_tpu.air import session
    from ray_tpu.models.gpt2 import GPT2, causal_lm_loss
    from ray_tpu.models.resnet import create_resnet
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.spmd import make_image_classifier_trainer

    rehearse, sz = config["rehearse"], config["sizes"]
    out = {"device": _device_report(rehearse)}
    cfg = gpt2_config(rehearse)
    spec = MeshSpec()
    mesh = spec.build(jax.devices()[:1])
    tokens = session.get_dataset_shard("tokens")
    state, batch, step, compile_s, hits = _compile_gpt2_step(
        cfg, mesh, spec,
        lambda tr: _shard_batch(tokens, tr, sz["batch"]))
    # the same params and batch under the XLA reference attention, before
    # the first step donates the state
    ref_model = GPT2(dataclasses.replace(cfg, attention_backend="reference"))
    ref_loss = float(jax.jit(lambda p, b: causal_lm_loss(
        ref_model.apply({"params": p}, b["input_ids"]), b["labels"]))(
            state["params"], batch))
    state, losses, secs = _run_steps(step, state, batch, sz["steps"])
    out["gpt2"] = {
        "compile_s": compile_s, "cache_hits": hits, "losses": losses,
        "step_s": secs, "ref_first_loss": ref_loss,
        "mosaic_calls": step.as_text().count("tpu_custom_call"),
        "state_platforms": _platforms(state),
        "peak_bytes": _peak_bytes()}
    del state, step, batch
    gc.collect()

    dtype = jnp.float32 if rehearse else jnp.bfloat16
    model = create_resnet("resnet50", num_classes=1000, dtype=dtype)
    trainer = make_image_classifier_trainer(
        model, mesh=mesh, spec=spec,
        input_shape=(1, sz["img"], sz["img"], 3))
    state = trainer.init(jax.random.PRNGKey(SEED))
    batch = _shard_batch(session.get_dataset_shard("images"), trainer,
                         sz["img_batch"])
    hits = _cache_hits()
    t0 = time.perf_counter()
    step = trainer.step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    state, losses, secs = _run_steps(step, state, batch, sz["steps"])
    out["resnet50"] = {"compile_s": compile_s, "cache_hits": hits[0],
                       "losses": losses, "step_s": secs,
                       "state_platforms": _platforms(state),
                       "peak_bytes": _peak_bytes()}
    session.report(out)


def check_task(config):
    """Checks that need the chip after the serve replica has gone: the
    second-process compile of the GPT-2 step (does the cache hit?), each
    served token teacher-forced through a cache-free forward of the same
    seed's params, and the paged decode kernel against its reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import attention as A
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    from ray_tpu.train.spmd import put_batch

    rehearse, sz = config["rehearse"], config["sizes"]
    out = {"device": _device_report(rehearse)}
    cfg = gpt2_config(rehearse)
    spec = MeshSpec()
    toks = np.asarray(config["tokens"], np.int32)
    *_, compile_s, hits = _compile_gpt2_step(
        cfg, spec.build(jax.devices()[:1]), spec,
        lambda tr: put_batch(tr, {"input_ids": toks, "labels": toks}))
    out["gpt2_recompile"] = {"compile_s": compile_s, "cache_hits": hits}

    # FlaxModelAdapter(seed=...) makes the replica's params again here
    adapter = FlaxModelAdapter("gpt2", config=cfg, seed=SEED)
    prompts, served = config["prompts"], config["served"]
    ids = np.zeros((len(prompts), sz["ref_seq"]), np.int32)
    for i, (p, g) in enumerate(zip(prompts, served)):
        ids[i, :len(p) + len(g)] = p + g
    logits = jax.jit(lambda p, x: adapter.model.apply(p, x).astype(
        jnp.float32))(adapter.params, jnp.asarray(ids))
    gaps, exact = [], 0
    for i, (p, g) in enumerate(zip(prompts, served)):
        rows = logits[i, len(p) - 1:len(p) - 1 + len(g)]      # [n, V]
        got = rows[jnp.arange(len(g)), jnp.asarray(g)]
        gaps.append(float(jnp.max(jnp.max(rows, axis=-1) - got)))
        exact += int(jnp.sum(jnp.argmax(rows, axis=-1) == jnp.asarray(g)))
    out["teacher_forced"] = {
        "max_gap": max(gaps), "exact": exact,
        "tokens": sum(len(g) for g in served),
        "logit_std": float(jnp.std(logits[0, 0]))}

    paged = {}
    shapes = {"gpt2-small": (8, 12, 12, 64), "llama-gqa": (8, 32, 8, 128)}
    for name, (B, H, Hkv, D) in shapes.items():
        bs, NB = 16, 64
        if rehearse:
            B, NB = 2, 4
        rng = np.random.RandomState(SEED % 2**31)
        dt = jnp.float32 if rehearse else jnp.bfloat16
        P = 1 + B * NB
        # the serving pool's form, two layers: [L, P, bs, Hkv * D]
        k = jnp.asarray(rng.randn(2, P, bs, Hkv * D), dt)
        v = jnp.asarray(rng.randn(2, P, bs, Hkv * D), dt)
        q = jnp.asarray(rng.randn(B, H, D), dt)
        bt = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(B, NB),
                         jnp.int32)
        ln = jnp.asarray(rng.randint(1, NB * bs + 1, (B,)), jnp.int32)
        ref = jax.jit(lambda *a: A.paged_attention_reference(
            *a, layer=1))(q, k, v, bt, ln)
        got = jax.jit(lambda *a: A.paged_attention_decode(
            *a, layer=1, interpret=rehearse))(q, k, v, bt, ln)
        paged[name] = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - ref.astype(jnp.float32))))
    out["paged_kernel_max_err"] = paged
    return out


class ChipProbe:
    """An actor granted one chip: its process reports what it sees."""

    def report(self, rehearse):
        return _device_report(rehearse)


def fourchip_worker(config):
    """Four-chip phase, inside one train worker granted all four chips:
    GPT-2 small on the dp2 x tp2 mesh against the same seed and batch on
    a one-device mesh."""
    import gc

    import jax

    from ray_tpu.air import session
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.spmd import default_spec_for

    rehearse, sz = config["rehearse"], config["sizes"]
    out = {"device": _device_report(rehearse)}
    devices = jax.devices()
    if len(devices) != 4:
        raise RuntimeError(f"granted 4 chips, jax sees {len(devices)}")
    cfg = gpt2_config(rehearse)
    tokens = session.get_dataset_shard("tokens")
    gbatch = 2 * sz["batch"]

    def run(spec, devs):
        state, batch, step, compile_s, hits = _compile_gpt2_step(
            cfg, spec.build(devs), spec,
            lambda tr: _shard_batch(tokens, tr, gbatch))
        per_dev = {}
        for leaf in jax.tree_util.tree_leaves(state):
            for s in leaf.addressable_shards:
                per_dev[s.device.id] = per_dev.get(s.device.id, 0) + \
                    s.data.nbytes
        total = sum(leaf.nbytes
                    for leaf in jax.tree_util.tree_leaves(state))
        hlo = step.as_text()
        state, losses, secs = _run_steps(step, state, batch, 3)
        return {"spec": spec.describe(), "compile_s": compile_s,
                "cache_hits": hits, "losses": losses, "step_s": secs,
                "mosaic_calls": hlo.count("tpu_custom_call"),
                "collectives": {op: hlo.count(f" {op}(") + hlo.count(
                    f" {op}-start(") for op in (
                        "all-reduce", "all-gather", "reduce-scatter",
                        "collective-permute", "all-to-all")},
                "state_bytes": total, "state_bytes_per_device": per_dev,
                "state_platforms": _platforms(state),
                "peak_bytes": _peak_bytes()}

    # the one-device run first, while nothing else is on device 0: at
    # global batch 32 it needs nearly the whole chip
    out["one"] = run(MeshSpec(), devices[:1])
    gc.collect()
    out["four"] = run(default_spec_for(4), devices)
    session.report(out)


# ------------------------------------------------------------ driver side

def chip_holders():
    """pid -> chip device files it holds open, over every process."""
    held = {}
    for pid in os.listdir("/proc"):
        if pid.isdigit():
            files = _chip_files(pid)
            if files:
                held[int(pid)] = files
    return held


def wait_chip_free(timeout_s=120.0):
    """The next phase's worker can only take the chip once the last one
    has let go of it: look at who holds the device files."""
    deadline = time.time() + timeout_s
    while True:
        held = chip_holders()
        if not held:
            return
        check(time.time() < deadline,
              f"chip still held after {timeout_s:.0f}s by {held}")
        time.sleep(0.2)


def native_libraries():
    """Build (or load) the three native libraries now, in this process,
    so that a missing compiler fails the smoke instead of degrading it to
    the Python fallbacks."""
    from ray_tpu._private import native_build, object_store, rpccore, sched
    object_store._lib()
    check(rpccore._lib() is not None,
          "librpcx.so could not be built or loaded (is g++ installed?)")
    check(sched._lib() is not None,
          "libschedcore.so could not be built or loaded")
    for name, how in sorted(native_build.STATUS.items()):
        log(f"native: {name} "
            + ("built from src/ in this run" if how == "built"
               else "loaded (stamp matches src/)"))
    check(len(native_build.STATUS) == 3, "expected three native libraries")


def fit(train_fn, config, datasets, tpus):
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.data_parallel_trainer import DataParallelTrainer
    resources = {"CPU": 1} if config["rehearse"] else {"TPU": tpus}
    result = DataParallelTrainer(
        train_fn, train_loop_config=config, datasets=datasets,
        scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker=resources)).fit()
    check(not result.error, f"train worker failed: {result.error}")
    return result.metrics


def check_device(name, dev, rehearse, count):
    """``count``: the devices this worker must see (unchecked in a
    rehearsal, where every process sees the CPU's virtual devices)."""
    log(f"[{name}] device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']} pid={dev['pid']} "
        f"TPU_VISIBLE_CHIPS={dev['visible_chips']} "
        f"chip_files={dev['chip_files']} cache_dir={dev['cache_dir']}")
    if not rehearse:
        check(dev["platform"] == "tpu", f"{name} ran on {dev['platform']}")
        check(dev["count"] == count,
              f"{name}: worker sees {dev['count']} devices, it was "
              f"granted {count} of the cluster's TPU resource")


def check_losses(name, losses):
    import math
    check(all(math.isfinite(x) for x in losses), f"{name}: loss not finite")
    check(losses[-1] < losses[0],
          f"{name}: loss did not fall on the repeated batch: {losses}")


def cold(row):
    """Says whether a compile time was paid in full or came from the
    persistent cache (which may have come with the machine)."""
    return (f"{row['cache_hits']} cache hit(s)" if row["cache_hits"]
            else "cold: no cache hit")


def fmt(xs, nd=3):
    return "[" + ", ".join(f"{x:.{nd}f}" for x in xs) + "]"


def token_rows(sz, vocab, n):
    import numpy as np
    rng = np.random.default_rng(SEED)
    return rng.integers(0, vocab, (n, sz["seq"]), dtype=np.int32)


def phase_train(ctx):
    import numpy as np

    from ray_tpu import data as rt_data
    sz, rehearse = ctx["sizes"], ctx["rehearse"]
    rows = token_rows(sz, ctx["vocab"], sz["batch"])
    rng = np.random.default_rng(SEED + 1)
    images = [{"image": rng.integers(0, 256, (sz["img"], sz["img"], 3),
                                     dtype=np.uint8),
               "label": np.int32(rng.integers(0, 1000))}
              for _ in range(sz["img_batch"])]
    datasets = {
        "tokens": rt_data.from_items(
            [{"input_ids": r, "labels": r} for r in rows], parallelism=4),
        "images": rt_data.from_items(images, parallelism=8)}
    m = fit(train_worker, {"rehearse": rehearse, "sizes": sz}, datasets, 1)
    check_device("train", m["device"], rehearse, ctx["tpus"])
    g, r = m["gpt2"], m["resnet50"]
    log(f"[train] gpt2-small b{sz['batch']} x s{sz['seq']}: compile "
        f"{g['compile_s']:.1f}s ({cold(g)}), smoke step "
        f"times {fmt(g['step_s'])}s, losses {fmt(g['losses'], 4)}, "
        f"reference-attention first loss {g['ref_first_loss']:.4f}, "
        f"mosaic calls {g['mosaic_calls']}, peak device bytes "
        f"{g['peak_bytes']}")
    log(f"[train] resnet50 b{sz['img_batch']} x {sz['img']}px: compile "
        f"{r['compile_s']:.1f}s ({cold(r)}), smoke step times "
        f"{fmt(r['step_s'])}s, "
        f"losses {fmt(r['losses'], 4)}, peak device bytes "
        f"{r['peak_bytes']}")
    check_losses("gpt2", g["losses"])
    check_losses("resnet50", r["losses"])
    check(abs(g["losses"][0] - g["ref_first_loss"]) <= LOSS_TOL,
          f"gpt2 first-step loss {g['losses'][0]} vs reference attention "
          f"{g['ref_first_loss']}: more than {LOSS_TOL} apart")
    if not rehearse:
        check(g["mosaic_calls"] > 0,
              "the compiled GPT-2 step holds no tpu_custom_call: the "
              "Pallas kernels are not on the path")
        for row in (g, r):
            check(row["state_platforms"] == ["tpu"],
                  f"train state lives on {row['state_platforms']}")
    ctx["device"] = m["device"]
    ctx["first_compile_s"] = g["compile_s"]
    ctx["tokens"] = rows.tolist()


def phase_serve(ctx):
    import http.client
    import socket

    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer

    sz, rehearse = ctx["sizes"], ctx["rehearse"]
    rng = np.random.default_rng(SEED + 2)
    prompts = [rng.integers(0, ctx["vocab"], (n,)).tolist()
               for n in sz["prompts"]]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    options = {} if rehearse else {"ray_actor_options": {"num_tpus": 1}}
    dep = serve.deployment(name="smoke_llm", num_replicas=1,
                           max_concurrent_queries=16, **options)(LLMServer)
    t0 = time.perf_counter()
    handle = serve.run(
        dep.bind("gpt2", {"config": gpt2_config(rehearse), "seed": SEED},
                 {"num_blocks": sz["num_blocks"], "block_size": 16,
                  "max_seq_len": gpt2_config(rehearse).n_positions,
                  "max_running": 8, "max_prefill_tokens": 1024}),
        name="smoke_llm", route_prefix="/smoke_llm", http_port=port,
        _blocking_timeout=600.0)
    log(f"[serve] replica up in {time.perf_counter() - t0:.1f}s "
        f"(params initialised, {sz['num_blocks']} x 16-token KV pages)")
    try:
        def payload(i):
            return {"tokens": prompts[i],
                    "max_new_tokens": sz["new_tokens"]}

        def unary(i):
            t0 = time.perf_counter()
            out = ray_tpu.get(handle.remote(payload(i)), timeout=600.0)
            return out["tokens"], time.perf_counter() - t0

        served, secs = {}, {}
        served[0], secs["unary p0 (cold: compiles)"] = unary(0)
        t0 = time.perf_counter()
        streamed = [t for ch in handle.stream(payload(0))
                    for t in ch["tokens"]]
        secs["handle.stream p0"] = time.perf_counter() - t0
        check(streamed == served[0],
              f"handle.stream tokens differ from unary: {streamed} vs "
              f"{served[0]}")

        t0 = time.perf_counter()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        conn.request("POST", "/smoke_llm",
                     json.dumps(dict(payload(1), stream=True)),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        check(resp.status == 200
              and resp.getheader("Content-Type") == "text/event-stream",
              f"HTTP stream answered {resp.status} "
              f"{resp.getheader('Content-Type')}")
        sse = []
        for line in resp.fp:
            line = line.strip()
            if line == b"data: [DONE]":
                break
            if line.startswith(b"data: "):
                sse.extend(json.loads(line[6:]).get("tokens", []))
        conn.close()
        secs["HTTP stream p1 (cold)"] = time.perf_counter() - t0
        served[1], secs["unary p1"] = unary(1)
        check(sse == served[1],
              f"HTTP-streamed tokens differ from unary: {sse} vs "
              f"{served[1]}")

        t0 = time.perf_counter()
        refs = [handle.remote(payload(i)) for i in range(2, len(prompts))]
        for i, ref in enumerate(refs, start=2):
            served[i] = ray_tpu.get(ref, timeout=900.0)["tokens"]
        secs[f"{len(refs)} concurrent unary (cold)"] = \
            time.perf_counter() - t0
        for i, toks in served.items():
            check(len(toks) == sz["new_tokens"],
                  f"request {i} returned {len(toks)} tokens")
        n_requests = 4 + len(refs)
        check(n_requests >= 8, "fewer than 8 requests")

        m = ray_tpu.get(handle.options("__llm_metrics__").remote(),
                        timeout=60.0)
        dev = m["device"]
        log(f"[serve] {n_requests} requests, prompts of "
            f"{min(sz['prompts'])}-{max(sz['prompts'])} tokens, "
            f"{sz['new_tokens']} new tokens each; smoke request times: "
            + "; ".join(f"{k} {v:.1f}s" for k, v in secs.items()))
        log(f"[serve] __llm_metrics__: params on platform="
            f"{dev['platform']} kind={dev['device_kind']}, peak device "
            f"bytes {dev['peak_bytes_in_use']}, finished "
            f"{m['finished_total']}, generated "
            f"{m['generated_tokens_total']} tokens, ttft p50 "
            f"{m['ttft_p50_s']:.3f}s, itl p50 {m['itl_p50_s']:.4f}s "
            "(smoke timings, compiles included)")
        check(m["failed_total"] == 0 and m["shed_total"] == 0,
              f"engine failed/shed requests: {m}")
        if not rehearse:
            check(dev["platform"] == "tpu",
                  f"the replica's params live on {dev['platform']}: it "
                  "serves from the CPU")
            check(dev["device_kind"] == ctx["device"]["kind"],
                  f"replica device {dev['device_kind']} != train device")
    finally:
        serve.shutdown()
    ctx["prompts"] = prompts
    ctx["served"] = [served[i] for i in range(len(prompts))]


def phase_check(ctx):
    import ray_tpu
    sz, rehearse = ctx["sizes"], ctx["rehearse"]
    options = {"num_cpus": 1} if rehearse else {"num_tpus": 1}
    out = ray_tpu.get(ray_tpu.remote(**options)(check_task).remote(
        {"rehearse": rehearse, "sizes": sz, "tokens": ctx["tokens"],
         "prompts": ctx["prompts"], "served": ctx["served"]}),
        timeout=900.0)
    check_device("check", out["device"], rehearse, ctx["tpus"])
    rc, tf = out["gpt2_recompile"], out["teacher_forced"]
    log(f"[check] gpt2 step compiled again in a second process: "
        f"{rc['compile_s']:.1f}s with {rc['cache_hits']} cache hit(s), "
        f"against {ctx['first_compile_s']:.1f}s in the first "
        f"(cache at {out['device']['cache_dir']})")
    log(f"[check] teacher-forced reference: {tf['exact']}/{tf['tokens']} "
        f"served tokens are the reference argmax; largest gap between a "
        f"served token's reference logit and the row maximum "
        f"{tf['max_gap']:.4f} (logit std {tf['logit_std']:.3f}, tolerance "
        f"{LOGIT_TOL})")
    log(f"[check] paged_attention_decode vs paged_attention_reference, "
        f"max abs err: {out['paged_kernel_max_err']} (tolerance "
        f"{PAGED_TOL})")
    check(tf["max_gap"] <= LOGIT_TOL,
          f"a served token's reference logit is {tf['max_gap']} below the "
          f"maximum (tolerance {LOGIT_TOL})")
    for name, err in out["paged_kernel_max_err"].items():
        check(err <= PAGED_TOL, f"paged kernel {name}: max err {err}")
    if not rehearse:
        check(rc["cache_hits"] >= 1,
              "the second process's compile of the GPT-2 step did not hit "
              "the compile cache")


def phase_isolation(ctx):
    import ray_tpu
    rehearse = ctx["rehearse"]
    options = {"num_cpus": 1} if rehearse else {"num_tpus": 1}
    probe = ray_tpu.remote(**options)(ChipProbe)
    actors = [probe.remote() for _ in range(2)]
    try:
        a, b = ray_tpu.get([x.report.remote(rehearse) for x in actors],
                           timeout=300.0)
        # asked again once both have taken their chip: the same two
        # processes answer, so both were alive at once
        again = ray_tpu.get([x.report.remote(rehearse) for x in actors],
                            timeout=60.0)
    finally:
        for x in actors:
            ray_tpu.kill(x)
    for w in (a, b):
        check_device("isolation", w, rehearse, 1)
    check(a["pid"] != b["pid"], "one process hosts both one-chip actors")
    check([w["pid"] for w in again] == [a["pid"], b["pid"]],
          "a one-chip worker was replaced while the other came up")
    if not rehearse:
        for w in (a, b):
            check(len(w["chip_files"]) == 1,
                  f"a one-chip worker holds {w['chip_files']}")
        check(a["chip_files"] != b["chip_files"],
              f"both one-chip workers hold {a['chip_files']}")
        check(a["visible_chips"] != b["visible_chips"],
              "both workers were granted the same chip")
    log("[isolation] two one-chip workers alive at once, one device each, "
        f"different chips: {a['chip_files']} and {b['chip_files']}")


def phase_four(ctx):
    from ray_tpu import data as rt_data
    sz, rehearse = ctx["sizes"], ctx["rehearse"]
    rows = token_rows(sz, ctx["vocab"], 2 * sz["batch"])
    datasets = {"tokens": rt_data.from_items(
        [{"input_ids": r, "labels": r} for r in rows], parallelism=4)}
    m = fit(fourchip_worker, {"rehearse": rehearse, "sizes": sz},
            datasets, 4)
    check_device("four", m["device"], rehearse, 4)
    one, four = m["one"], m["four"]
    for name, r in (("one device", one), ("four devices", four)):
        log(f"[four] {name} {r['spec']} global b{2 * sz['batch']} x "
            f"s{sz['seq']}: compile {r['compile_s']:.1f}s ({cold(r)}), "
            f"smoke step "
            f"times {fmt(r['step_s'])}s, losses {fmt(r['losses'], 4)}, "
            f"mosaic calls {r['mosaic_calls']}, collectives "
            f"{r['collectives']}, state bytes {r['state_bytes']} spread "
            f"as {r['state_bytes_per_device']}, peak device-0 bytes "
            f"{r['peak_bytes']}")
    check_losses("four-device gpt2", four["losses"])
    worst = max(abs(a - b) for a, b in zip(one["losses"], four["losses"]))
    log(f"[four] largest loss difference per step, dp2 x tp2 against one "
        f"device: {worst:.5f} (tolerance {MESH_TOL})")
    check(worst <= MESH_TOL, f"losses differ by {worst}")
    check(sum(four["collectives"].values()) > 0,
          "the four-device step holds no collective")
    check(len(four["state_bytes_per_device"]) == 4,
          "the state does not touch all four devices")
    check(max(four["state_bytes_per_device"].values())
          < four["state_bytes"],
          "one device holds the whole train state: nothing is sharded")
    if not rehearse:
        check(four["mosaic_calls"] > 0,
              "the four-device step holds no tpu_custom_call")
        check(four["state_platforms"] == ["tpu"],
              f"state lives on {four['state_platforms']}")
    ctx["device"] = m["device"]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never prints the ok line")
    args = ap.parse_args()
    rehearse = args.rehearse

    asked = os.environ.get("JAX_PLATFORMS", "")
    if not rehearse and asked and "tpu" not in asked.split(","):
        sys.exit(f"chip_smoke: JAX_PLATFORMS={asked} keeps jax off the "
                 "TPU; this script only passes on the chip")
    # the driver stays off the chip; workers granted chips get their own
    # platform setting from the raylet
    os.environ["JAX_PLATFORMS"] = "cpu"

    import ray_tpu
    t_start = time.perf_counter()
    ctx = {"rehearse": rehearse, "sizes": sizes(rehearse),
           "vocab": gpt2_config(rehearse).vocab_size}
    try:
        native_libraries()
        # no num_tpus: chip discovery is part of what is smoked
        ray_tpu.init(num_cpus=8, object_store_memory=2 * 1024**3,
                     _system_config={"prestart_workers": False})
        try:
            ctx["tpus"] = int(ray_tpu.cluster_resources().get("TPU", 0))
            log(f"cluster resources: {ray_tpu.cluster_resources()}")
            check(rehearse or ctx["tpus"] == args.chips,
                  f"chip discovery found {ctx['tpus']} TPU chip(s), this "
                  f"run needs {args.chips}")
            phases = ([phase_isolation, phase_four] if args.chips == 4
                      else [phase_train, phase_serve, phase_check])
            for phase in phases:
                wait_chip_free()
                t0 = time.perf_counter()
                phase(ctx)
                log(f"[{phase.__name__[6:]}] phase passed in "
                    f"{time.perf_counter() - t0:.0f}s")
            wait_chip_free()
        finally:
            ray_tpu.shutdown()
        if "jax" in sys.modules:
            from jax._src import xla_bridge
            check(not xla_bridge.backends_are_initialized(),
                  "the driver process initialised a jax backend")
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    log(f"chip_smoke: all phases passed in "
        f"{time.perf_counter() - t_start:.0f}s")
    if rehearse:
        sys.exit(3)   # a rehearsal is not a chip run
    dev = ctx["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)


if __name__ == "__main__":
    main()
