"""Flagship benchmark: ResNet-50 synthetic-data training throughput,
driven END-TO-END through the framework (ray_tpu.init → DataParallelTrainer
→ TPU worker → session.get_dataset_shard → double-buffered device feed).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Metric: ResNet-50 images/sec/chip, bf16, synthetic ImageNet shapes —
the reference's headline Train benchmark (reference:
release/air_tests/air_benchmarks/mlperf-train/resnet50_ray_air.py:194-196,
torchvision resnet50 under TorchTrainer/DDP). Baseline: 2500 images/s per
A100. The headline number is measured INSIDE a framework-managed train
worker; a raw-JAX control run (same step function, no framework) runs
first in its own subprocess so the orchestration overhead is visible as
`raw_img_per_sec` vs the headline.

A second model row rides in the same JSON line: GPT-2 small (the
flagship `entry()` model) train-step tokens/s/chip + MFU, measured in the
same framework-managed worker (`gpt2_*` keys).

Process layout:
  - the TPU is touched only by short-lived subprocesses (raw control, and
    the framework's TPU worker), one after the other: a chip belongs to
    one process at a time, so the raw control has exited before the
    framework's worker starts, and the driver itself stays on CPU;
  - no chip = fail: the flagship mode exits non-zero and prints no metric
    when jax finds no TPU, a compile fails or either model row fails.
    There is no CPU re-run;
  - subprocesses run in their own session; a timed-out one gets its
    whole process group SIGKILLed and reaped, so a wedged PJRT client
    cannot keep the chip;
  - timing takes the best of several windows.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_IMG_PER_SEC_PER_CHIP = 2500.0  # A100 MLPerf-class ResNet-50 DDP

METRIC = "resnet50_images_per_sec_per_chip"
UNIT = "images/s/chip"

_PEAK_BF16 = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]

# Round-4 re-measurement: the scoped-vmem override round 3 added
# (--xla_tpu_scoped_vmem_limit_kib=98304) is a 5% REGRESSION on this
# chip (111.2ms vs 105.7ms/step raw control, back-to-back) — the
# compiler's default vmem budget wins, so every path strips
# LIBTPU_INIT_ARGS from its subprocess env.

READY_MARKER = "#BENCH_BACKEND_READY"
INIT_TIMEOUT_S = float(os.environ.get("BENCH_INIT_TIMEOUT", 300))
RUN_TIMEOUT_S = float(os.environ.get("BENCH_RUN_TIMEOUT", 2400))


def _peak_flops(device_kind: str):
    kind = device_kind.lower()
    for key, peak in _PEAK_BF16:
        if key in kind:
            return peak
    raise KeyError(f"no bf16 peak known for device kind {device_kind!r}: "
                   "add it to _PEAK_BF16 with its source")


def _force_cpu_platform():
    """Pin jax to CPU before any backend init (host-plane sub-modes only:
    env var and live config, in case jax was already imported)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    for var in ("LIBTPU_INIT_ARGS", "TPU_LIBRARY_PATH"):
        os.environ.pop(var, None)
    import jax
    jax.config.update("jax_platforms", "cpu")


def _kill_group(proc):
    """SIGKILL a subprocess's whole session and reap it — a wedged PJRT
    client must not survive the attempt and keep the chip."""
    import signal
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        proc.kill()
    try:
        proc.wait(timeout=10)
    except Exception:
        pass


def _reap_framework_orphans():
    """Kill leftover ray_tpu node processes (gcs/raylet/workers). The
    framework driver spawns them with start_new_session=True, so killing
    the driver's group does NOT reach them — after a timed-out framework
    run the wedged train worker would keep the chip.
    The bench owns this box, so a cmdline sweep is safe."""
    import signal
    me = os.getpid()
    for pid_s in os.listdir("/proc"):
        if not pid_s.isdigit() or int(pid_s) == me:
            continue
        try:
            with open(f"/proc/{pid_s}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="ignore")
        except OSError:
            continue
        if "ray_tpu._private" in cmd or "ray_tpu/_private" in cmd:
            try:
                os.kill(int(pid_s), signal.SIGKILL)
            except OSError:
                pass


def _emit(value, vs_baseline, **extras):
    line = {"metric": METRIC, "value": value, "unit": UNIT,
            "vs_baseline": vs_baseline}
    line.update(extras)
    print(json.dumps(line), flush=True)


# --------------------------------------------------------------- train body

def _require_tpu():
    """The flagship rows are device metrics: no TPU, no number."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py: jax found no TPU (platform "
            f"{devices[0].platform!r}); the flagship mode has no CPU run")
    return devices


def _timed_windows(step, state, next_batch, windows, steps_per_window,
                   warmup):
    """Best per-step seconds over ``windows`` windows; each window ends
    on block_until_ready of the loss (serial state dependency)."""
    import jax
    for _ in range(warmup):
        state, metrics = step(state, next_batch())
    jax.block_until_ready(metrics["loss"])
    best_dt = None
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps_per_window):
            state, metrics = step(state, next_batch())
        jax.block_until_ready(metrics["loss"])
        dt = (time.perf_counter() - t0) / steps_per_window
        best_dt = dt if best_dt is None else min(best_dt, dt)
    return best_dt


def _compile_step(trainer, state, batch):
    """AOT-compile the step; returns (step, compile_s, flops/step)."""
    t0 = time.perf_counter()
    step = trainer.step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    ca = step.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
    return step, compile_s, float(ca.get("flops", 0.0)) or None


def bench_loop(make_feed=None):
    """The measured training loop. Runs inside the raw-control subprocess
    AND inside the framework train worker — identical math either way.

    Returns a dict of measurements. `make_feed(trainer, batch_size)`:
    optional factory returning an endless iterator of device-committed
    batches (the framework path feeds uint8 batches through the Dataset
    pipeline with double-buffered device_put); None = one resident batch
    (raw control — no input cost, the pure-compute ceiling).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.resnet import create_resnet
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.spmd import make_image_classifier_trainer, put_batch

    devices = _require_tpu()
    n_dev = jax.local_device_count()
    batch = int(os.environ.get("BENCH_BATCH", 256)) * n_dev
    image_size, dtype = 224, jnp.bfloat16

    spec = MeshSpec(dp=n_dev)
    mesh = spec.build(devices[:n_dev])
    model = create_resnet("resnet50", num_classes=1000, dtype=dtype)
    trainer = make_image_classifier_trainer(
        model, mesh=mesh, spec=spec,
        input_shape=(1, image_size, image_size, 3))
    state = trainer.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    feed = None
    if make_feed is not None:
        feed = make_feed(trainer, batch)
        resident = next(feed)  # template for compile (uint8 pipeline)
    else:
        images = rng.standard_normal(
            (batch, image_size, image_size, 3), dtype=np.float32)
        labels = rng.integers(0, 1000, (batch,), dtype=np.int32)
        resident = put_batch(trainer, {"image": images, "label": labels})

    step, compile_s, flops = _compile_step(trainer, state, resident)
    best_dt = _timed_windows(
        step, state, (lambda: resident) if feed is None else
        (lambda: next(feed)), windows=8, steps_per_window=10, warmup=3)

    out = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_chips": n_dev,
        "batch_per_chip": batch // n_dev,
        "step_time_ms": round(best_dt * 1e3, 2),
        "compile_s": round(compile_s, 2),
        "img_per_sec": round(batch / best_dt, 2),
        "img_per_sec_per_chip": round(batch / best_dt / n_dev, 2),
    }
    if flops:
        peak = _peak_flops(devices[0].device_kind)
        out["flops_per_step"] = flops
        # cost_analysis reports the per-device post-partition module,
        # so per-device flops over per-chip peak IS per-chip MFU
        out["mfu"] = round(flops / best_dt / peak, 4)
        out["peak_bf16_flops_per_chip"] = peak
    return out


def gpt2_loop():
    """GPT-2 small train-step throughput (tokens/s/chip + MFU) — the
    flagship `entry()` model, measured as one donated pjit'd step with a
    device-resident batch. Reference analogue: the HF GPT-2 fine-tune
    config in BASELINE.md (train/huggingface/huggingface_trainer.py:157)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.spmd import make_causal_lm_trainer, put_batch

    devices = _require_tpu()
    n_dev = jax.local_device_count()
    cfg = GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                     n_layer=12, n_head=12,
                     attention_backend="flash", dtype=jnp.bfloat16)
    batch = int(os.environ.get("BENCH_GPT2_BATCH", 16)) * n_dev
    seq = 1024

    spec = MeshSpec(dp=n_dev)
    mesh = spec.build(devices[:n_dev])
    trainer = make_causal_lm_trainer(cfg, mesh=mesh, spec=spec)
    state = trainer.init(jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    resident = put_batch(trainer, {"input_ids": tokens, "labels": tokens})

    step, compile_s, flops = _compile_step(trainer, state, resident)
    best_dt = _timed_windows(step, state, lambda: resident, windows=6,
                             steps_per_window=5, warmup=2)

    out = {
        "gpt2_batch_per_chip": batch // n_dev,
        "gpt2_seq_len": seq,
        "gpt2_step_time_ms": round(best_dt * 1e3, 2),
        "gpt2_compile_s": round(compile_s, 2),
        "gpt2_tokens_per_sec_per_chip": round(
            batch * seq / best_dt / n_dev, 1),
    }
    if flops:
        # per-device flops (post-partition module) over per-chip peak
        out["gpt2_mfu"] = round(
            flops / best_dt / _peak_flops(devices[0].device_kind), 4)
    return out


# ----------------------------------------------------- raw control (subproc)

def _raw_main():
    """Raw-JAX control run: same loop, no framework. Own process so the
    chip is released before the framework worker claims it."""
    devices = _require_tpu()
    print(f"{READY_MARKER} platform={devices[0].platform}", flush=True)
    print(json.dumps(bench_loop()), flush=True)


def _run_raw_control():
    # reader THREAD + events, not blocking readline: a hung PJRT init
    # prints nothing, and a blocked readline would defeat both timeouts
    # (the round-1 failure mode this supervisor exists for)
    import threading

    env = dict(os.environ, _BENCH_RAW="1")
    env.pop("LIBTPU_INIT_ARGS", None)
    from ray_tpu.common.config import compile_cache_env
    compile_cache_env(env)
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                            stdout=subprocess.PIPE, text=True, env=env,
                            start_new_session=True)
    lines: list = []
    got_ready = threading.Event()
    done = threading.Event()

    def reader():
        for line in proc.stdout:
            line = line.strip()
            if line.startswith(READY_MARKER):
                got_ready.set()
            elif line:
                lines.append(line)
        done.set()
        got_ready.set()  # EOF: a child that died early is not a hang

    threading.Thread(target=reader, daemon=True).start()
    if not got_ready.wait(INIT_TIMEOUT_S):
        _kill_group(proc)
        return None, "raw control: backend init timed out"
    if not done.wait(RUN_TIMEOUT_S):
        _kill_group(proc)
        return None, "raw control: run timed out"
    if proc.wait() == 0:
        for line in reversed(lines):
            try:
                return json.loads(line), None
            except ValueError:
                continue
    return None, f"raw control exited rc={proc.returncode} w/o a result"


# ------------------------------------------------- framework path (headline)

def _train_loop_per_worker(config):
    """Runs inside the framework-managed TPU worker."""
    from ray_tpu.air import session

    shard = session.get_dataset_shard("train")

    make_feed = None
    if shard is not None:
        def make_feed(trainer, batch_size):
            # Synthetic-data regime, same as the reference benchmark
            # (resnet50_ray_air synthetic mode): the Dataset's batches are
            # transferred once via the double-buffered device iterator and
            # then cycled device-resident, so the window holds no input
            # cost (ROADMAP S7: the feed has never been inside one).
            import itertools
            cached = list(shard.iter_device_batches(
                batch_size=batch_size,
                sharding=trainer.batch_shardings,
                drop_last=True, pad_to_batch=False))
            return itertools.cycle(cached)
    res = bench_loop(make_feed=make_feed)
    res.update(gpt2_loop())
    session.report(res)


def _framework_main():
    """Driver: CPU-pinned; the TPU belongs to the train worker."""
    os.environ["JAX_PLATFORMS"] = "cpu"

    import numpy as np

    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.data_parallel_trainer import DataParallelTrainer

    # no num_tpus: the raylet discovers the chips (no chip, no TPU
    # resource, and the trainer's placement fails)
    ray_tpu.init(num_cpus=4, object_store_memory=2 * 1024**3,
                 _system_config={"prestart_workers": False})
    try:
        # synthetic ImageNet shard: uint8 images (the wire format a real
        # ingest pipeline would ship), labels int32
        n_imgs, img = 1024, 224
        rng = np.random.default_rng(0)
        items = [{"image": rng.integers(0, 256, (img, img, 3),
                                        dtype=np.uint8),
                  "label": np.int32(rng.integers(0, 1000))}
                 for _ in range(n_imgs)]
        train_ds = rt_data.from_items(items, parallelism=8)

        if not ray_tpu.cluster_resources().get("TPU"):
            raise SystemExit("bench.py: the cluster found no TPU chip")
        trainer = DataParallelTrainer(
            _train_loop_per_worker,
            datasets={"train": train_ds},
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker={"TPU": 1}))
        result = trainer.fit()
        if result.error:
            raise RuntimeError(result.error)
        return result.metrics
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------- data-ingest microbench

def _data_ingest_main():
    """Data-ingest microbenchmark (ISSUE 1): N blocks through a
    read(sleep) -> map(sleep) two-stage chain, bulk vs streaming
    executor.  Reports blocks/s and time-to-first-batch per mode.  The
    two map_batches stages use different remote_opts so they do NOT fuse
    — the stage skew is what bulk execution serializes and streaming
    overlaps.  Prints one JSON line; also merged into the flagship line
    as ingest_* keys by the supervisor."""
    _force_cpu_platform()
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rt_data

    n_blocks = int(os.environ.get("BENCH_INGEST_BLOCKS", 16))
    read_s = float(os.environ.get("BENCH_INGEST_READ_S", 0.2))
    map_s = float(os.environ.get("BENCH_INGEST_MAP_S", 0.2))
    n_cpus = 4

    def read_sim(b):
        time.sleep(read_s)
        return b

    def map_sim(b):
        time.sleep(map_s)
        return b

    ray_tpu.init(num_cpus=n_cpus, object_store_memory=512 * 1024**2,
                 _system_config={"prestart_workers": False})
    out = {}
    try:
        # warm the worker pool so neither mode pays spawn cost
        rt_data.range(8, parallelism=8).map(lambda x: x).take_all()
        # streaming keeps in-flight ~= cores so the head map task is not
        # queued behind the whole read wave
        os.environ["RTPU_DATA_MAX_INFLIGHT_TASKS"] = str(n_cpus)
        for mode, key in (("0", "bulk"), ("1", "streaming")):
            os.environ["RTPU_DATA_STREAMING"] = mode
            t0 = time.perf_counter()
            ds = (rt_data.range(n_blocks * 16, parallelism=n_blocks)
                  .map_batches(read_sim, batch_format="numpy", num_cpus=1)
                  .map_batches(map_sim, batch_format="numpy"))
            it = ds.iter_batches(batch_size=16, batch_format="numpy")
            first = next(it)
            t_first = time.perf_counter() - t0
            n = 1 + sum(1 for _ in it)
            t_total = time.perf_counter() - t0
            assert n == n_blocks and len(first) == 16
            out[f"{key}_time_to_first_batch_s"] = round(t_first, 3)
            out[f"{key}_total_s"] = round(t_total, 3)
            out[f"{key}_blocks_per_s"] = round(n_blocks / t_total, 2)
        out["blocks"] = n_blocks
        out["chain_latency_s"] = read_s + map_s
        out["ttfb_speedup"] = round(
            out["bulk_time_to_first_batch_s"]
            / out["streaming_time_to_first_batch_s"], 2)
        out["throughput_vs_bulk"] = round(
            out["streaming_blocks_per_s"] / out["bulk_blocks_per_s"], 3)
    finally:
        ray_tpu.shutdown()
    print(json.dumps({"metric": "data_ingest", **out}), flush=True)


def _run_ingest_bench():
    """Run the ingest microbench in a subprocess (CPU-only, cheap) and
    return its keys prefixed ingest_*, or {} on any failure — it must
    never sink the flagship line."""
    env = dict(os.environ, _BENCH_DATA_INGEST="1", JAX_PLATFORMS="cpu")
    env.pop("LIBTPU_INIT_ARGS", None)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            stdout=subprocess.PIPE, text=True, timeout=180, env=env)
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                row = json.loads(line)
                if row.get("metric") == "data_ingest":
                    row.pop("metric")
                    return {f"ingest_{k}": v for k, v in row.items()}
    except Exception:
        pass
    return {}


# --------------------------------------------------- checkpoint microbench

def _ckpt_bench_main():
    """Checkpoint-engine microbench (_BENCH_CKPT=1): how long the train
    step is blocked per save, sync vs async, on a multi-MB pytree.

    Each mode runs the same loop: mutate state, save, then "train" for
    BENCH_CKPT_STEP_MS (the compute an async writer overlaps). Sync mode
    (RTPU_CKPT_ASYNC=0) blocks for snapshot+write+checksum+fsync+commit;
    async blocks only for the host snapshot (+ any backpressure when the
    previous write hasn't landed). No cluster needed; one JSON line."""
    import shutil
    import tempfile

    import numpy as np

    from ray_tpu.checkpoint import AsyncCheckpointer, CheckpointManager

    mb = float(os.environ.get("BENCH_CKPT_MB", 64))
    saves = int(os.environ.get("BENCH_CKPT_SAVES", 4))
    step_ms = float(os.environ.get("BENCH_CKPT_STEP_MS", 200))
    n_leaves = 8
    leaf_elems = max(1, int(mb * 1024 ** 2 / 4 / n_leaves))
    rng = np.random.default_rng(0)
    state = {"params": {f"w{i}": rng.standard_normal(leaf_elems)
                        .astype(np.float32) for i in range(n_leaves)},
             "step": np.zeros((), np.int32)}
    total_mb = sum(a.nbytes for a in state["params"].values()) / 1024 ** 2
    out = {"pytree_mb": round(total_mb, 1), "saves": saves,
           "step_ms": step_ms}
    for mode in ("sync", "async"):
        os.environ["RTPU_CKPT_ASYNC"] = "1" if mode == "async" else "0"
        root = tempfile.mkdtemp(prefix=f"rtpu_ckpt_bench_{mode}_")
        try:
            mgr = CheckpointManager(root, num_to_keep=2)
            ck = AsyncCheckpointer(mgr)
            blocked = []
            t_all = time.perf_counter()
            for s in range(saves):
                state["step"] = state["step"] + 1
                t0 = time.perf_counter()
                ck.save(s, state)
                blocked.append(time.perf_counter() - t0)
                time.sleep(step_ms / 1e3)  # the overlapped train step
            ck.finalize()
            wall = time.perf_counter() - t_all
            assert mgr.latest_committed() == saves - 1, \
                f"{mode}: expected step {saves - 1} committed"
            stats = ck.stats
            out[f"{mode}_blocked_ms_per_save"] = round(
                1e3 * sum(blocked) / saves, 2)
            out[f"{mode}_snapshot_ms_mean"] = round(
                sum(st.snapshot_ms for st in stats) / saves, 2)
            out[f"{mode}_write_ms_mean"] = round(
                sum(st.write_ms for st in stats) / saves, 2)
            out[f"{mode}_wall_s"] = round(wall, 3)
        finally:
            shutil.rmtree(root, ignore_errors=True)
    out["blocked_frac_vs_sync"] = round(
        out["async_blocked_ms_per_save"]
        / max(out["sync_blocked_ms_per_save"], 1e-9), 4)
    print(json.dumps({"metric": "checkpoint", **out}), flush=True)


def _chaos_bench_main():
    """Chaos smoke (_BENCH_CHAOS=1): fault→detect→recover latency for
    the two headline faults.

    Phase A — worker SIGKILL at a chosen task count (chaos schedule):
    detect = the raylet's WORKER_DIED event vs the kill timestamp in the
    chaos log; recover = the killed task's retried result landing.

    Phase B — preemption notice on a worker node: drain time (the
    raylet's own NODE_PREEMPTED accounting) and failover time (notice →
    GCS marks the node dead) from the structured event stream.

    One JSON line; recorded in PERF.md."""
    import tempfile

    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private import worker as wmod
    from ray_tpu._private.cluster_utils import Cluster

    out = {}

    def events(w, label):
        evs = w.call_sync(w.gcs, "list_events", {"limit": 1000})
        return [e for e in evs if e.get("label") == label]

    # ---- phase A: worker kill detect/recover
    log_path = os.path.join(tempfile.mkdtemp(prefix="rtpu_chaos_bench_"),
                            "chaos.jsonl")
    os.environ["RTPU_CHAOS"] = json.dumps({"seed": 1, "schedule": [
        {"site": "worker.execute", "op": "kill", "at": 3,
         "proc": "worker"}]})
    os.environ["RTPU_CHAOS_LOG"] = log_path
    ray_tpu.init(num_cpus=1, object_store_memory=128 * 1024 * 1024)
    try:
        @ray_tpu.remote(max_retries=3)
        def unit(x):
            return x

        t0 = time.perf_counter()
        for i in range(6):
            assert ray_tpu.get(unit.remote(i), timeout=120) == i
        out["workload_wall_s"] = round(time.perf_counter() - t0, 3)
        w = wmod._global_worker
        kill = next(r for r in chaos.read_log(log_path)
                    if r["op"] == "kill")
        died = events(w, "WORKER_DIED")
        assert died, "worker death was never detected"
        out["worker_kill_detect_ms"] = round(
            1e3 * (died[0]["timestamp"] - kill["ts"]), 1)
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RTPU_CHAOS", None)
        os.environ.pop("RTPU_CHAOS_LOG", None)

    # ---- phase B: preemption drain + failover
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        info = cluster.add_node(num_cpus=2, resources={"spot": 1})
        cluster.connect()
        cluster.wait_for_nodes()
        w = wmod._global_worker

        @ray_tpu.remote(max_retries=3, resources={"spot": 0.1})
        def on_spot(x):
            return x + 1

        assert ray_tpu.get(on_spot.remote(1), timeout=60) == 2
        t0 = time.time()
        cluster.preempt_node(info, grace_s=2.0)
        deadline = time.monotonic() + 30
        dead_at = None
        while time.monotonic() < deadline:
            n = next(n for n in ray_tpu.nodes()
                     if n["node_id"] == info["node_id"])
            if not n["alive"]:
                dead_at = time.time()
                break
            time.sleep(0.1)
        assert dead_at is not None, "preempted node never died"
        notice = events(w, "PREEMPTION_NOTICE")
        preempted = events(w, "NODE_PREEMPTED")
        assert notice and preempted
        out["preempt_drain_s"] = round(
            preempted[0]["fields"].get("drain_s", 0.0), 3)
        out["preempt_failover_s"] = round(dead_at - t0, 3)
        out["preempt_notice_to_dead_s"] = round(
            dead_at - notice[0]["timestamp"], 3)
    finally:
        cluster.shutdown()
    print(json.dumps({"metric": "chaos", **out}), flush=True)


# ------------------------------------------------------ state-engine bench


def _dag_bench_main():
    """Compiled-DAG bench (_BENCH_DAG=1): 3-stage actor pipeline,
    compiled channels vs dynamic ``.execute()`` dispatch (ROADMAP item
    3; gates >=5x per-hop latency and >=3x pipelined throughput on the
    1-core CI box). Also reports a 256 KB-payload variant (plasmax
    ring-slot path) and the ring-reuse segment delta. One JSON line;
    recorded in PERF.md."""
    import statistics

    import numpy as np

    import ray_tpu
    from ray_tpu._private import worker as wmod
    from ray_tpu.dag import InputNode

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    out = {}
    try:
        @ray_tpu.remote
        class Stage:
            def step(self, x):
                return x

        with InputNode() as inp:
            s1, s2, s3 = Stage.bind(), Stage.bind(), Stage.bind()
            pipe = s3.step.bind(s2.step.bind(s1.step.bind(inp)))

        def lat(fn, n):
            xs = []
            for _ in range(n):
                t0 = time.perf_counter()
                fn()
                xs.append(time.perf_counter() - t0)
            return statistics.median(xs)

        # dynamic: per-exec latency + pipelined throughput (refs
        # submitted without waiting, gathered in one get)
        ray_tpu.get(pipe.execute(0))  # actor warmup
        dyn_exec_s = lat(lambda: ray_tpu.get(pipe.execute(0)), 50)
        n = 200
        t0 = time.perf_counter()
        refs = [pipe.execute(i) for i in range(n)]
        ray_tpu.get(refs, timeout=300)
        dyn_rate = n / (time.perf_counter() - t0)

        cpipe = pipe.compile()
        assert cpipe._compiled, "pipeline failed to compile"
        cpipe.execute(0)  # channel warmup
        cmp_exec_s = lat(lambda: cpipe.execute(0), 200)
        t0 = time.perf_counter()
        futs = [cpipe.execute_async(i) for i in range(1000)]
        for f in futs:
            f.result(60)
        cmp_rate = 1000 / (time.perf_counter() - t0)

        out["dynamic_per_hop_us"] = round(1e6 * dyn_exec_s / 3, 1)
        out["compiled_per_hop_us"] = round(1e6 * cmp_exec_s / 3, 1)
        out["per_hop_speedup"] = round(dyn_exec_s / cmp_exec_s, 2)
        out["dynamic_pipelined_per_s"] = round(dyn_rate, 1)
        out["compiled_pipelined_per_s"] = round(cmp_rate, 1)
        out["throughput_speedup"] = round(cmp_rate / dyn_rate, 2)

        # 256 KB activations through the plasmax ring slots: steady-state
        # latency + the segment-usage delta across 100 triggers (must be
        # flat — seal/unseal reuse, docs/COMPILED_DAGS.md)
        arr = np.zeros(32 * 1024, dtype=np.float64)
        for _ in range(4):  # >= ring depth: lazy slots exist before t0
            cpipe.execute(arr)
        w = wmod._global_worker
        s0 = w.plasma.stats()
        big_s = lat(lambda: cpipe.execute(arr), 100)
        s1_ = w.plasma.stats()
        out["compiled_256k_per_hop_us"] = round(1e6 * big_s / 3, 1)
        out["ring_used_bytes_delta"] = \
            s1_["used_bytes"] - s0["used_bytes"]
        out["ring_created_delta"] = \
            s1_["num_created"] - s0["num_created"]
        cpipe.teardown()
    finally:
        ray_tpu.shutdown()
    out["gate_per_hop_5x"] = out["per_hop_speedup"] >= 5.0
    out["gate_throughput_3x"] = out["throughput_speedup"] >= 3.0
    print(json.dumps({"metric": "compiled_dag", **out}), flush=True)


def _net_bench_main():
    """Cross-node transport bench (_BENCH_NET=1): two raylets on one
    machine restricted to TCP (distinct ``RTPU_NODE_IP`` aliases +
    ``RTPU_NET_FORCE_TCP``, the same harness as tests/test_netx.py).
    Measures (a) bulk object pull throughput through the netx ``px_*``
    plane vs the asyncio chunk-RPC pull baseline — gated against the
    63 MiB/s SCALE.md round-5 aggregate — (b) direct-lane actor-call
    RTT across "hosts", (c) compiled-DAG cross-host execute latency.
    Env: NET_BENCH_SMOKE=1 shrinks the run (CI smoke); NET_BENCH_MB
    overrides the object size. One JSON line; recorded in PERF.md."""
    import statistics

    import numpy as np

    import ray_tpu
    from ray_tpu._private import netx
    from ray_tpu._private.cluster_utils import Cluster
    from ray_tpu._private.netx import endpoints
    from ray_tpu.dag import InputNode

    smoke = bool(os.environ.get("NET_BENCH_SMOKE"))
    mb = int(os.environ.get("NET_BENCH_MB", "32" if smoke else "256"))
    iters = 30 if smoke else 200
    store = max(512, 3 * mb) * 1024 * 1024

    def two_host_cluster(netx_on):
        os.environ["RTPU_NODE_IP"] = "127.0.0.1"
        os.environ["RTPU_NET_FORCE_TCP"] = "1"
        os.environ["RTPU_NETX"] = "1" if netx_on else "0"
        endpoints._reset_for_tests()
        netx.reset_client_for_tests()
        cluster = Cluster(initialize_head=True,
                          head_node_args={"num_cpus": 2,
                                          "resources": {"hosta": 4},
                                          "object_store_memory": store})
        cluster.add_node(num_cpus=2, resources={"hostb": 4},
                         object_store_memory=store,
                         env_overrides={
                             "RTPU_NODE_IP": "127.0.0.2",
                             "RTPU_NET_FORCE_TCP": "1",
                             "RTPU_NETX": "1" if netx_on else "0"})
        cluster.connect()
        cluster.wait_for_nodes()
        return cluster

    def pull_mib_s():
        # object sealed on "host" B first (the probe task runs next to
        # it, zero-copy), THEN the driver-side get times the pure
        # cross-host transfer + local map
        @ray_tpu.remote(resources={"hostb": 1})
        def make(n):
            return np.ones(n, dtype=np.uint8)

        @ray_tpu.remote(resources={"hostb": 1})
        def probe(x):
            return int(x[0])

        n = mb * 1024 * 1024
        ref = make.remote(n)
        assert ray_tpu.get(probe.remote(ref), timeout=600) == 1
        t0 = time.perf_counter()
        arr = ray_tpu.get(ref, timeout=600)
        dt = time.perf_counter() - t0
        assert arr.shape == (n,)
        return mb / dt

    out = {"object_mb": mb}
    cluster = two_host_cluster(netx_on=False)
    try:
        out["asyncio_pull_mib_s"] = round(pull_mib_s(), 1)
    finally:
        cluster.shutdown()

    cluster = two_host_cluster(netx_on=True)
    try:
        out["netx_pull_mib_s"] = round(pull_mib_s(), 1)

        @ray_tpu.remote(resources={"hostb": 1})
        class Echo:
            def e(self, x):
                return x

        a = Echo.remote()
        ray_tpu.get(a.e.remote(0), timeout=120)  # lane warm
        xs = []
        for i in range(iters):
            t0 = time.perf_counter()
            ray_tpu.get(a.e.remote(i), timeout=60)
            xs.append(time.perf_counter() - t0)
        out["actor_call_rtt_us"] = round(1e6 * statistics.median(xs), 1)

        with InputNode() as inp:
            s1 = Echo.options(resources={"hosta": 1}).bind()
            s2 = Echo.options(resources={"hostb": 1}).bind()
            pipe = s2.e.bind(s1.e.bind(inp))
        cpipe = pipe.compile()
        try:
            assert cpipe._compiled, "cross-host pipeline failed to compile"
            cpipe.execute(0)  # channel warmup
            xs = []
            for i in range(iters):
                t0 = time.perf_counter()
                cpipe.execute(i)
                xs.append(time.perf_counter() - t0)
            out["dag_cross_host_exec_us"] = round(
                1e6 * statistics.median(xs), 1)
        finally:
            cpipe.teardown()
    finally:
        cluster.shutdown()
        for k in ("RTPU_NODE_IP", "RTPU_NET_FORCE_TCP", "RTPU_NETX"):
            os.environ.pop(k, None)

    out["pull_speedup_vs_asyncio"] = round(
        out["netx_pull_mib_s"] / max(out["asyncio_pull_mib_s"], 0.1), 2)
    # SCALE.md round-5 broadcast baseline: 63 MiB/s aggregate on the
    # asyncio chunk-RPC path — the netx plane must beat it outright
    out["gate_pull_63mibs"] = out["netx_pull_mib_s"] >= 63.0
    print(json.dumps({"metric": "net", **out}), flush=True)


def _state_bench_main():
    """State-engine microbench (_BENCH_STATE=1): with 10k+ drained
    tasks in the GCS task table, measure (a) list_tasks first-page p50
    latency, (b) a full paginated walk, (c) the naive full-dump (one
    legacy RPC carrying the whole table — what every list call did
    before pagination), and (d) the head-node (GCS) RSS delta from
    holding the bounded table. One JSON line; recorded in PERF.md."""
    import statistics
    import subprocess as sp

    import ray_tpu
    from ray_tpu._private import worker as wmod
    from ray_tpu.experimental.state import api as state_api

    n = int(os.environ.get("STATE_BENCH_TASKS", 10_000))

    def gcs_rss() -> int:
        pid = int(sp.check_output(
            ["pgrep", "-f", "ray_tpu._private.gcs_main"]).split()[0])
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    return int(line.split()[1]) * 1024
        return 0

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    out = {}
    try:
        rss0 = gcs_rss()

        @ray_tpu.remote
        def sb_noop(i):
            return i

        t0 = time.perf_counter()
        ray_tpu.get(sb_noop.remote_batch([(i,) for i in range(n)]),
                    timeout=900)
        out["drain_s"] = round(time.perf_counter() - t0, 2)
        # wait for the event pipeline to settle into the table
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            s = state_api.summarize_tasks()
            tracked = s["by_state"].get("FINISHED", 0) + s["dropped"]
            if tracked >= n:
                break
            time.sleep(0.5)
        out["tasks_tracked"] = s["total"]
        out["tasks_dropped"] = s["dropped"]
        out["gcs_rss_delta_mb"] = round((gcs_rss() - rss0) / 1e6, 1)

        lat = []
        for _ in range(30):
            t0 = time.perf_counter()
            page = state_api.list_tasks(page_size=1000)
            lat.append(time.perf_counter() - t0)
        assert len(page) == 1000
        out["page1k_p50_ms"] = round(
            1e3 * statistics.median(lat), 2)
        t0 = time.perf_counter()
        full = state_api.list_tasks()
        out["paginated_walk_s"] = round(time.perf_counter() - t0, 3)
        out["rows_walked"] = len(full)
        # naive legacy path: the whole table in ONE rpc reply
        w = wmod._global_worker
        lat = []
        for _ in range(10):
            t0 = time.perf_counter()
            rows = w.call_sync(w.gcs, "list_tasks", {}, timeout=120)
            lat.append(time.perf_counter() - t0)
        assert len(rows) == len(full)
        out["naive_full_dump_p50_ms"] = round(
            1e3 * statistics.median(lat), 2)
    finally:
        ray_tpu.shutdown()
    # deterministic table-cost measurement (live GCS RSS deltas get
    # absorbed by allocator arenas): n records through a fresh table
    # in this process
    from ray_tpu._private.gcs import TaskEventTable

    def rss_self() -> int:
        with open(f"/proc/{os.getpid()}/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    return int(line.split()[1]) * 1024
        return 0

    r0 = rss_self()
    table = TaskEventTable(cap=max(n, 32768))
    now = time.time()
    for i in range(n):
        tid = f"{i:032x}"
        table.apply({"task_id": tid, "state": "PENDING_SCHEDULING",
                     "ts": now, "name": "sb_noop", "job_id": "01"})
        table.apply({"task_id": tid, "state": "RUNNING", "ts": now,
                     "node_id": "n" * 32, "worker_pid": 1234})
        table.apply({"task_id": tid, "state": "FINISHED", "ts": now})
    out["table_cost_mb"] = round((rss_self() - r0) / 1e6, 2)
    print(json.dumps({"metric": "state_engine", "n_tasks": n, **out}),
          flush=True)


# ------------------------------------------------------- serve data-plane bench

class _BenchSeqCounter:
    """Named-actor sequence so the Nth-constructed replica can tell it is
    the Nth (the skewed-replica picker below)."""

    def __init__(self):
        self.n = 0

    def next(self):
        self.n += 1
        return self.n


def _serve_bench_main():
    """Serve data-plane benchmark (_BENCH_SERVE=1): closed-loop clients
    through the handle and HTTP paths, reporting RPS/p50/p99 for
    round-robin vs power-of-two-choices routing under skewed replica
    load, and fixed-window vs adaptive micro-batching (idle p50 +
    loaded RPS). CPU-only; one JSON line."""
    _force_cpu_platform()
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu import serve

    duration = float(os.environ.get("BENCH_SERVE_DURATION", 3.0))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 8))
    service_ms = float(os.environ.get("BENCH_SERVE_SERVICE_MS", 5.0))
    skew = float(os.environ.get("BENCH_SERVE_SKEW", 10.0))

    def closed_loop(fn, n_clients, dur):
        lat, errors = [], [0]
        lock = threading.Lock()
        stop = time.perf_counter() + dur

        def worker():
            local = []
            while time.perf_counter() < stop:
                t0 = time.perf_counter()
                try:
                    fn()
                except Exception:
                    with lock:
                        errors[0] += 1
                    continue
                local.append(time.perf_counter() - t0)
            with lock:
                lat.extend(local)

        threads = [threading.Thread(target=worker)
                   for _ in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if not lat:
            return {"rps": 0.0, "p50_ms": 0.0, "p99_ms": 0.0,
                    "errors": errors[0]}
        arr = np.asarray(lat)
        return {"rps": round(len(lat) / dur, 1),
                "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 2),
                "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 2),
                "errors": errors[0]}

    class SkewedEcho:
        """One replica serves at service_s, its sibling skew× slower —
        the asymmetry blind round-robin cannot see."""

        def __init__(self, service_s, skew_factor):
            import ray_tpu as rt
            try:
                ctr = rt.get_actor("BENCH_SERVE_SEQ")
            except Exception:
                try:
                    ctr = rt.remote(name="BENCH_SERVE_SEQ",
                                    lifetime="detached")(
                        _BenchSeqCounter).remote()
                except Exception:  # sibling replica won the race
                    ctr = rt.get_actor("BENCH_SERVE_SEQ")
            idx = rt.get(ctr.next.remote())
            self.delay = service_s * (skew_factor if idx % 2 == 0
                                      else 1.0)

        def __call__(self, x):
            time.sleep(self.delay)
            return x

    def make_batched(adaptive_mode):
        class BatchedEcho:
            @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05,
                         adaptive=adaptive_mode, submit_timeout_s=30.0)
            def run(self, items):
                time.sleep(0.002)  # one fixed-cost "model step" per flush
                return list(items)

            def __call__(self, x):
                return self.run(x)
        return BatchedEcho

    ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024,
                 _system_config={"prestart_workers": False})
    out = {"duration_s": duration, "clients": clients,
           "service_ms": service_ms, "skew": skew}
    try:
        # ---- routing: 2 skewed replicas, handle path, rr vs p2c ----
        h = serve.run(
            serve.deployment(num_replicas=2, max_concurrent_queries=32)(
                SkewedEcho).bind(service_ms / 1e3, skew),
            name="routing", route_prefix="/skew", http_port=8200)

        def handle_call():
            ray_tpu.get(h.remote(1), timeout=30.0)

        for _ in range(8):
            handle_call()  # warm replicas + router telemetry
        for policy in ("round_robin", "p2c"):
            os.environ["RTPU_SERVE_ROUTING"] = policy
            time.sleep(1.2)  # let a fresh replica_load long-poll land
            st = closed_loop(handle_call, clients, duration)
            for k, v in st.items():
                out[f"route_{policy}_{k}"] = v
        if out["route_round_robin_rps"]:
            out["p2c_vs_rr_rps"] = round(
                out["route_p2c_rps"] / out["route_round_robin_rps"], 3)

        # ---- HTTP path (p2c), same skewed deployment ----
        import urllib.request
        proxy = ray_tpu.get_actor("SERVE_PROXY")
        port = ray_tpu.get(proxy.get_port.remote())

        def http_call():
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/skew?x=1", timeout=30).read()

        http_call()
        st = closed_loop(http_call, clients, duration)
        for k, v in st.items():
            out[f"http_{k}"] = v

        # ---- batching: fixed window vs adaptive ----
        for mode in ("fixed", "adaptive"):
            dep = serve.deployment(
                num_replicas=1, max_concurrent_queries=64)(
                make_batched(mode == "adaptive"))
            hb = serve.run(dep.options(name=f"Batched_{mode}").bind(),
                           name=f"batch_{mode}",
                           route_prefix=f"/batch_{mode}", http_port=None)

            def batch_call(hb=hb):
                ray_tpu.get(hb.remote(1), timeout=30.0)

            batch_call()
            idle = closed_loop(batch_call, 1, duration)  # idle queue
            loaded = closed_loop(batch_call, 2 * clients, duration)
            out[f"batch_{mode}_idle_p50_ms"] = idle["p50_ms"]
            out[f"batch_{mode}_idle_p99_ms"] = idle["p99_ms"]
            out[f"batch_{mode}_rps"] = loaded["rps"]
            out[f"batch_{mode}_p99_ms"] = loaded["p99_ms"]
        if out["batch_adaptive_idle_p50_ms"]:
            out["adaptive_idle_p50_speedup"] = round(
                out["batch_fixed_idle_p50_ms"]
                / out["batch_adaptive_idle_p50_ms"], 2)
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
    print(json.dumps({"metric": "serve_dataplane", **out}), flush=True)


# ------------------------------------------------------- serve HA bench

def _serve_ha_bench_main():
    """Serve control-plane HA benchmark (_BENCH_SERVE_HA=1): request
    success rate and latency under sustained load during (a) a
    health-gated rolling update and (b) a controller SIGKILL +
    journal recovery. The acceptance bar is ZERO failed requests in
    both windows — the data plane must not notice the control plane.
    CPU-only; one JSON line."""
    _force_cpu_platform()
    import signal
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu import serve

    duration = float(os.environ.get("BENCH_SERVE_HA_DURATION", 8.0))
    clients = int(os.environ.get("BENCH_SERVE_HA_CLIENTS", 6))

    def versioned(v):
        @serve.deployment(num_replicas=2, name="HA",
                          max_concurrent_queries=32,
                          user_config={"v": v},
                          graceful_shutdown_timeout_s=10.0)
        class HA:
            def __init__(self):
                self.v = None

            def reconfigure(self, cfg):
                self.v = cfg["v"]

            def __call__(self, x):
                time.sleep(0.005)
                return self.v

        return HA

    class _Phase:
        """Closed-loop load whose samples are binned into named phases
        by wall-clock markers."""

        def __init__(self):
            self.lock = threading.Lock()
            self.samples = []  # (t_done, latency_s, ok)
            self.stop = threading.Event()

        def worker(self, fn):
            while not self.stop.is_set():
                t0 = time.perf_counter()
                ok = True
                try:
                    fn()
                except Exception:
                    ok = False
                with self.lock:
                    self.samples.append(
                        (time.time(), time.perf_counter() - t0, ok))

        def window(self, t_start, t_end):
            with self.lock:
                rows = [(lat, ok) for t, lat, ok in self.samples
                        if t_start <= t <= t_end]
            lats = [lat for lat, ok in rows if ok]
            return {
                "total": len(rows),
                "failed": sum(1 for _, ok in rows if not ok),
                "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 2)
                if lats else 0.0,
                "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 2)
                if lats else 0.0,
            }

    ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024,
                 _system_config={"prestart_workers": False})
    out = {"duration_s": duration, "clients": clients}
    try:
        h = serve.run(versioned(1).bind(), http_port=None)
        ray_tpu.get(h.remote(0), timeout=30.0)
        ph = _Phase()

        def call():
            ray_tpu.get(h.remote(0), timeout=30.0)

        threads = [threading.Thread(target=ph.worker, args=(call,))
                   for _ in range(clients)]
        for t in threads:
            t.start()
        time.sleep(duration / 4)

        # (a) health-gated rolling update under load
        t0 = time.time()
        serve.run(versioned(2).bind(), http_port=None,
                  _blocking_timeout=120.0)
        t1 = time.time()
        out["rolling_s"] = round(t1 - t0, 2)
        for k, v in ph.window(t0, t1).items():
            out[f"rolling_{k}"] = v
        time.sleep(duration / 4)

        # (b) controller SIGKILL + journal recovery under load
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        pid = ray_tpu.get(ctrl.get_controller_info.remote(),
                          timeout=10.0)["pid"]
        t2 = time.time()
        os.kill(pid, signal.SIGKILL)
        recovered = None
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                info = ray_tpu.get(ctrl.get_controller_info.remote(),
                                   timeout=5.0)
                st = ray_tpu.get(
                    ctrl.get_deployment_statuses.remote(), timeout=5.0)
                if info["pid"] != pid and info["recovered"] and \
                        st.get("HA", {}).get("status") == "HEALTHY":
                    recovered = time.time()
                    break
            except Exception:
                pass
            time.sleep(0.2)
        t3 = time.time()
        out["ctrl_recovery_s"] = round(
            (recovered or t3) - t2, 2)
        out["ctrl_recovered"] = bool(recovered)
        for k, v in ph.window(t2, t3).items():
            out[f"ctrl_kill_{k}"] = v
        time.sleep(duration / 4)
        ph.stop.set()
        for t in threads:
            t.join()
        whole = ph.window(0, time.time())
        out["overall_total"] = whole["total"]
        out["overall_failed"] = whole["failed"]
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
    print(json.dumps({"metric": "serve_ha", **out}), flush=True)


def _gameday_bench_main():
    """Game-day SLO bench (_BENCH_GAMEDAY=1): run the builtin scenarios
    end to end — open-loop load with diurnal/flash-crowd shapes + the
    seeded chaos schedule + rolling updates — and report CLIENT-side
    p99/p99.9, error-budget burn, failed-request count, and whether the
    ledger reconciled exactly with the server-side records
    (docs/GAMEDAY.md). CPU-only; one JSON line.

    Env: BENCH_GAMEDAY_SCENARIOS (default "flagship,flash-crowd"),
    BENCH_GAMEDAY_SCALE (phase-duration multiplier, default 1.0)."""
    _force_cpu_platform()
    from ray_tpu.gameday import load_scenario, run_scenario

    names = [n.strip() for n in os.environ.get(
        "BENCH_GAMEDAY_SCENARIOS", "flagship,flash-crowd").split(",")
        if n.strip()]
    scale = float(os.environ.get("BENCH_GAMEDAY_SCALE", 1.0))
    out = {"scale": scale, "scenarios": {}}
    for name in names:
        sc = load_scenario(name)
        result = run_scenario(sc, scale=scale, dashboard_port=18471)
        rep = result.report
        o = rep["overall"]
        recon = rep["reconciliation"]
        out["scenarios"][name] = {
            "seed": rep["seed"],
            "requests": o["total"],
            "admitted": o["admitted"],
            "shed": o["shed"],
            "failed": o["failed"],
            "p50_ms": o["p50_ms"],
            "p99_ms": o["p99_ms"],
            "p999_ms": o["p999_ms"],
            "availability_burn": rep["slo"]["availability_burn"],
            "latency_burn": rep["slo"].get("latency_burn"),
            "reconciled": recon["ok"],
            "chaos_fired": len(rep.get("chaos_fired") or []),
            "passed": rep["passed"],
        }
    print(json.dumps({"metric": "gameday", **out}), flush=True)


def _llm_bench_main():
    """LLM serving bench (_BENCH_LLM=1): the continuous-batching
    engine vs the static flush-by-window baseline under a skewed
    open-loop load (Poisson arrivals, bounded-Pareto output lengths),
    plus the paged-attention kernel numerics check. One JSON line:
    tokens/s, p50/p99 time-to-first-token (measured from the SCHEDULED
    arrival — open-loop discipline), makespan, and the gates the
    acceptance criteria name: continuous >= 1.5x static tokens/s with
    better p99 TTFT; paged kernel == whole-kv reference numerics.

    Env: LLM_BENCH_SMOKE=1 shrinks the run (CI smoke);
    LLM_BENCH_DURATION_S / LLM_BENCH_RPS override the load window.

    The toy adapter emulates model cost (3 ms/step + 0.2 ms/sequence;
    0.05 ms/prefill token): per-step cost is mostly FIXED, which is
    exactly the regime where continuous batching wins — a static batch
    runs its stragglers nearly alone while admitted work waits.

    Two fleet-serving sections ride along (docs/LLM_SERVING.md):
    radix prefix cache (warm vs cold under a Zipf-skewed
    shared-system-prompt tenant mix; gates >= 1.3x tokens/s, no-worse
    TTFT p99, identical outputs, hit ratio reported) and
    prefill/decode disaggregation (1 prefill + 1 decode engine with
    KV handoff vs 2 unified engines round-robin; gate: disagg TPOT
    p99 <= unified — decode never pays a prefill bubble)."""
    _force_cpu_platform()
    import random
    import threading

    from ray_tpu.serve.llm import (EngineConfig, LLMEngine,
                                   SamplingParams, ToyAdapter)

    smoke = bool(os.environ.get("LLM_BENCH_SMOKE"))
    # offered tokens/s must exceed the STATIC baseline's capacity
    # (~210 tok/s at these step costs: a flush-by-window batch runs at
    # its longest member's length) while staying well under the
    # continuous engine's (~1.7k tok/s) — that's the regime the gate
    # measures: same hardware budget, saturation only for the baseline
    duration = float(os.environ.get("LLM_BENCH_DURATION_S",
                                    2.5 if smoke else 10.0))
    rate = float(os.environ.get("LLM_BENCH_RPS",
                                25.0 if smoke else 40.0))
    rng = random.Random(1234)
    arrivals = []
    t = 0.0
    while True:
        t += rng.expovariate(rate)
        if t >= duration:
            break
        plen = rng.randint(8, 32)
        ntok = max(8, min(128, int(8 * rng.paretovariate(1.2))))
        arrivals.append((t, [rng.randrange(256)
                             for _ in range(plen)], ntok))

    def run(policy):
        eng = LLMEngine(
            ToyAdapter(step_delay_s=0.003, per_seq_delay_s=0.0002,
                       per_prefill_token_delay_s=0.00005),
            EngineConfig(max_running=8, max_waiting=100000,
                         max_prefill_tokens=256, num_blocks=4096,
                         block_size=16, max_seq_len=512,
                         policy=policy))
        results = []
        lock = threading.Lock()

        def consume(sched_abs, sid):
            cur, toks, first = 0, 0, None
            while True:
                ch = eng.poll(sid, cur, max_wait_s=30.0)
                if ch["tokens"] and first is None:
                    first = time.time()
                toks += len(ch["tokens"])
                cur = ch["cursor"]
                if ch["done"]:
                    break
            with lock:
                results.append(
                    (max(0.0, (first or time.time()) - sched_abs),
                     toks))

        threads = []
        t0 = time.time()
        for (ta, prompt, ntok) in arrivals:
            delay = t0 + ta - time.time()
            if delay > 0:
                time.sleep(delay)
            sid = eng.add_request(
                prompt, SamplingParams(max_new_tokens=ntok))
            th = threading.Thread(target=consume, args=(t0 + ta, sid))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        makespan = time.time() - t0
        eng.stop()
        ttfts = sorted(r[0] for r in results)
        tokens = sum(r[1] for r in results)

        def q(frac):
            return round(
                ttfts[min(len(ttfts) - 1, int(frac * len(ttfts)))]
                * 1e3, 2)

        return {"tokens": tokens,
                "makespan_s": round(makespan, 3),
                "tokens_per_s": round(tokens / makespan, 2),
                "ttft_p50_ms": q(0.50), "ttft_p99_ms": q(0.99)}

    cont = run("continuous")
    static = run("static")

    # ---- radix prefix cache: Zipf-skewed tenants share system prompts
    # Prefill cost dominates here (1 ms/prompt token): skipping the
    # cached shared-prefix pages is a direct throughput win.  The SAME
    # arrival schedule runs warm (radix cache on) and cold (off);
    # greedy decoding, so the token streams must be identical.
    p_duration = float(os.environ.get("LLM_BENCH_PREFIX_DURATION_S",
                                      1.5 if smoke else 6.0))
    p_rate = 16.0 if smoke else 30.0
    n_tenants = 6
    prng = random.Random(77)
    zipf_w = [1.0 / (i + 1) ** 1.4 for i in range(n_tenants)]
    prefixes = [[random.Random(f"sys:{i}").randrange(256)
                 for _ in range(48)] for i in range(n_tenants)]
    p_arrivals = []
    t = 0.0
    while True:
        t += prng.expovariate(p_rate)
        if t >= p_duration:
            break
        tenant = prng.choices(range(n_tenants), weights=zipf_w)[0]
        suffix = [prng.randrange(256)
                  for _ in range(prng.randint(6, 14))]
        p_arrivals.append((t, prefixes[tenant] + suffix,
                           prng.randint(6, 12)))
    p_prompt_tokens = sum(len(a[1]) for a in p_arrivals)

    def run_prefix(enable):
        eng = LLMEngine(
            ToyAdapter(step_delay_s=0.001, per_seq_delay_s=0.0001,
                       per_prefill_token_delay_s=0.001),
            EngineConfig(max_running=8, max_waiting=100000,
                         max_prefill_tokens=512, num_blocks=4096,
                         block_size=16, max_seq_len=512,
                         enable_prefix_cache=enable))
        outs = [None] * len(p_arrivals)
        ttfts = [0.0] * len(p_arrivals)

        def consume(i, sched_abs, sid):
            cur, toks, first = 0, [], None
            while True:
                ch = eng.poll(sid, cur, max_wait_s=30.0)
                if ch["tokens"] and first is None:
                    first = time.time()
                toks.extend(ch["tokens"])
                cur = ch["cursor"]
                if ch["done"]:
                    break
            outs[i] = toks
            ttfts[i] = max(0.0, (first or time.time()) - sched_abs)

        threads = []
        t0 = time.time()
        for i, (ta, prompt, ntok) in enumerate(p_arrivals):
            delay = t0 + ta - time.time()
            if delay > 0:
                time.sleep(delay)
            sid = eng.add_request(
                prompt, SamplingParams(max_new_tokens=ntok))
            th = threading.Thread(target=consume,
                                  args=(i, t0 + ta, sid))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        makespan = time.time() - t0
        hit_tokens = int(eng.metrics().get("cache_hit_tokens_total", 0))
        eng.stop()
        q = sorted(ttfts)
        tokens = sum(len(o) for o in outs)
        return {"tokens_per_s": round(tokens / makespan, 2),
                "ttft_p99_ms": round(
                    q[min(len(q) - 1, int(0.99 * len(q)))] * 1e3, 2),
                "hit_tokens": hit_tokens, "outs": outs}

    warm = run_prefix(True)
    cold = run_prefix(False)
    prefix_ratio = round(warm["tokens_per_s"]
                         / max(cold["tokens_per_s"], 1e-9), 2)
    prefix_hit_ratio = round(warm["hit_tokens"]
                             / max(p_prompt_tokens, 1), 3)

    # ---- prefill/decode disaggregation vs unified, same 2-engine budget
    # Disagg: one prefill-role engine hands the prompt KV (inline blob —
    # the serve path ships the identical blob over plasmax ring slots)
    # to one decode-role engine, which never runs a prefill.  Unified:
    # two engines round-robin, each interleaving prefills into its
    # decode batch.  The prefill bubbles (~50 ms at these costs) land
    # in the unified engines' inter-token gaps — TPOT p99 is the gate.
    d_duration = 1.5 if smoke else 6.0
    d_rate = 5.0 if smoke else 8.0
    drng = random.Random(99)
    d_arrivals = []
    t = 0.0
    while True:
        t += drng.expovariate(d_rate)
        if t >= d_duration:
            break
        plen = 32 + drng.randint(8, 16)
        d_arrivals.append((t, [drng.randrange(256) for _ in range(plen)],
                           drng.randint(8, 16)))

    def _mk_eng():
        return LLMEngine(
            ToyAdapter(step_delay_s=0.001, per_seq_delay_s=0.0001,
                       per_prefill_token_delay_s=0.001),
            EngineConfig(max_running=8, max_waiting=100000,
                         max_prefill_tokens=512, num_blocks=4096,
                         block_size=16, max_seq_len=512))

    def _drain_timed(eng, sid, first=None):
        """Poll a stream to completion; returns (t_first, t_last, n)."""
        cur, n, last = 0, 0, None
        while True:
            ch = eng.poll(sid, cur, max_wait_s=30.0)
            if ch["tokens"]:
                if first is None:
                    first = time.time()
                last = time.time()
                n += len(ch["tokens"])
            cur = ch["cursor"]
            if ch["done"]:
                break
        return first, last or first or time.time(), n

    def run_disagg():
        pre, dec = _mk_eng(), _mk_eng()
        rows = []
        lock = threading.Lock()

        def one(sched_abs, prompt, ntok):
            sp = SamplingParams(max_new_tokens=ntok)
            sid = pre.prefill_export(prompt, sp)
            t_first, _, _ = _drain_timed(pre, sid)
            export = pre.take_export(sid) or {}
            first_tok = export.get("first_token")
            if first_tok is None:
                return
            sid2 = dec.adopt_request(prompt, int(first_tok),
                                     export.get("kv"), sp)
            _, t_last, n = _drain_timed(dec, sid2, first=t_first)
            with lock:
                rows.append((max(0.0, t_first - sched_abs),
                             (t_last - t_first) / max(n - 1, 1), n))

        threads = []
        t0 = time.time()
        for (ta, prompt, ntok) in d_arrivals:
            delay = t0 + ta - time.time()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=one,
                                  args=(t0 + ta, prompt, ntok))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        pre.stop()
        dec.stop()
        return rows

    def run_unified_pair():
        engs = [_mk_eng(), _mk_eng()]
        rows = []
        lock = threading.Lock()

        def one(eng, sched_abs, prompt, ntok):
            sid = eng.add_request(
                prompt, SamplingParams(max_new_tokens=ntok))
            t_first, t_last, n = _drain_timed(eng, sid)
            with lock:
                rows.append((max(0.0, t_first - sched_abs),
                             (t_last - t_first) / max(n - 1, 1), n))

        threads = []
        t0 = time.time()
        for i, (ta, prompt, ntok) in enumerate(d_arrivals):
            delay = t0 + ta - time.time()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(
                target=one, args=(engs[i % 2], t0 + ta, prompt, ntok))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        for e in engs:
            e.stop()
        return rows

    def _q99(rows, idx):
        vals = sorted(r[idx] for r in rows)
        if not vals:
            return 0.0
        return round(vals[min(len(vals) - 1,
                              int(0.99 * len(vals)))] * 1e3, 2)

    disagg_rows = run_disagg()
    unified_rows = run_unified_pair()

    # paged-attention kernel numerics vs the whole-kv reference
    # (tier-1 re-asserts this; the bench records the number)
    import numpy as np

    import jax.numpy as jnp
    from ray_tpu.ops import attention as A
    r2 = np.random.RandomState(0)
    B, H, Hkv, D, bs, NB = 3, 8, 2, 16, 8, 4
    lengths = jnp.asarray([5, 17, 30], jnp.int32)
    # the serving pool's form: [layers, pages, page, Hkv * D]
    k_pages = jnp.asarray(r2.randn(2, 1 + B * NB, bs, Hkv * D), jnp.float32)
    v_pages = jnp.asarray(r2.randn(2, 1 + B * NB, bs, Hkv * D), jnp.float32)
    bt = jnp.asarray(np.arange(1, 1 + B * NB).reshape(B, NB), jnp.int32)
    qq = jnp.asarray(r2.randn(B, H, D), jnp.float32)
    ref = A.paged_attention_reference(qq, k_pages, v_pages, bt, lengths,
                                      layer=1)
    ker = A.paged_attention_decode(qq, k_pages, v_pages, bt, lengths,
                                   layer=1, interpret=True)
    max_err = float(jnp.max(jnp.abs(ref - ker)))

    ratio = round(cont["tokens_per_s"]
                  / max(static["tokens_per_s"], 1e-9), 2)
    out = {
        "metric": "llm_serving",
        "requests": len(arrivals),
        "load_window_s": duration,
        "offered_rps": rate,
        "continuous_tokens_per_s": cont["tokens_per_s"],
        "static_tokens_per_s": static["tokens_per_s"],
        "tokens_per_s_ratio": ratio,
        "continuous_ttft_p50_ms": cont["ttft_p50_ms"],
        "continuous_ttft_p99_ms": cont["ttft_p99_ms"],
        "static_ttft_p50_ms": static["ttft_p50_ms"],
        "static_ttft_p99_ms": static["ttft_p99_ms"],
        "continuous_makespan_s": cont["makespan_s"],
        "static_makespan_s": static["makespan_s"],
        "paged_kernel_max_err": max_err,
        "gate_throughput_ok": ratio >= 1.5,
        "gate_ttft_ok":
            cont["ttft_p99_ms"] <= static["ttft_p99_ms"],
        "gate_numerics_ok": max_err < 1e-4,
        # radix prefix cache (warm vs cold, same Zipf tenant schedule)
        "prefix_requests": len(p_arrivals),
        "prefix_warm_tokens_per_s": warm["tokens_per_s"],
        "prefix_cold_tokens_per_s": cold["tokens_per_s"],
        "prefix_tokens_per_s_ratio": prefix_ratio,
        "prefix_warm_ttft_p99_ms": warm["ttft_p99_ms"],
        "prefix_cold_ttft_p99_ms": cold["ttft_p99_ms"],
        "prefix_hit_ratio": prefix_hit_ratio,
        "gate_prefix_throughput_ok": prefix_ratio >= 1.3,
        "gate_prefix_ttft_ok":
            warm["ttft_p99_ms"] <= cold["ttft_p99_ms"],
        "gate_prefix_identical_ok": warm["outs"] == cold["outs"],
        # prefill/decode disaggregation vs unified (2 engines each)
        "disagg_requests": len(d_arrivals),
        "disagg_ttft_p99_ms": _q99(disagg_rows, 0),
        "unified_ttft_p99_ms": _q99(unified_rows, 0),
        "disagg_tpot_p99_ms": _q99(disagg_rows, 1),
        "unified_tpot_p99_ms": _q99(unified_rows, 1),
        "gate_disagg_tpot_ok":
            _q99(disagg_rows, 1) <= _q99(unified_rows, 1),
    }
    print(json.dumps(out), flush=True)


# ----------------------------------------------------------------- supervise

def _supervise():
    """Raw control subprocess, then the framework run, then ONE JSON
    line. Any failure (no chip among them) exits non-zero with no
    metric line."""
    ingest = _run_ingest_bench()  # CPU-only, runs before the chip is used
    _reap_framework_orphans()  # a crashed prior run must not linger
    raw, err = _run_raw_control()
    if raw is None:
        sys.exit(f"bench.py: {err}")
    # the raw control has exited: the chip is free for the train worker
    env = dict(os.environ, _BENCH_FRAMEWORK="1")
    env.pop("LIBTPU_INIT_ARGS", None)
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                            stdout=subprocess.PIPE, text=True, env=env,
                            start_new_session=True)
    fw = None
    try:
        out, _ = proc.communicate(timeout=RUN_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        _kill_group(proc)
        _reap_framework_orphans()
        sys.exit("bench.py: framework run timed out")
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                fw = json.loads(line)
                break
            except ValueError:
                continue
    if proc.returncode or fw is None or "img_per_sec_per_chip" not in fw:
        sys.exit("bench.py: framework run produced no result "
                 f"(rc={proc.returncode})")
    fw["raw_img_per_sec_per_chip"] = raw["img_per_sec_per_chip"]
    fw["framework_vs_raw"] = round(
        fw["img_per_sec_per_chip"] / raw["img_per_sec_per_chip"], 4)
    fw.update(ingest)
    value = fw.pop("img_per_sec_per_chip")
    _emit(value, round(value / BASELINE_IMG_PER_SEC_PER_CHIP, 4), **fw)


def _trace_bench_main():
    """Tracing bench (_BENCH_TRACE=1): (a) span-pipeline throughput —
    record_span + flush rates on a discard sender; (b) the overhead
    gate — serve closed-loop RPS through the handle path with default
    sampling vs RTPU_TRACING=0, one fresh cluster per mode so replicas
    inherit the env. Gate (PERF.md): on/off RPS ratio >= 0.95."""
    _force_cpu_platform()
    import threading

    import numpy as np

    from ray_tpu._private import tracing

    duration = float(os.environ.get("BENCH_TRACE_DURATION", 3.0))
    clients = int(os.environ.get("BENCH_TRACE_CLIENTS", 8))
    service_ms = float(os.environ.get("BENCH_TRACE_SERVICE_MS", 2.0))
    out = {"duration_s": duration, "clients": clients,
           "service_ms": service_ms}

    # ---- (a) span pipeline microbench: pure record + flush cost ----
    # forced sample=1.0 so this measures the RECORDED path, not the
    # early head-sample drop (the serve section below measures the
    # default-sampling mix)
    prev_sample = os.environ.get("RTPU_TRACE_SAMPLE")
    os.environ["RTPU_TRACE_SAMPLE"] = "1.0"
    tracing.refresh()
    tracing.set_sender(lambda p: True)  # count-and-discard
    try:
        n = 200_000
        now = time.time()
        t0 = time.perf_counter()
        for i in range(n):
            tracing.record_span("bench-trace", f"s{i}", "bench",
                                phase="execute", start_ts=now,
                                end_ts=now + 0.001)
        record_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        while tracing.pending_count():
            tracing.flush()
        flush_dt = time.perf_counter() - t0
        out["record_spans_per_s"] = round(n / record_dt)
        out["record_us_per_span"] = round(record_dt / n * 1e6, 3)
        out["flush_spans_per_s"] = round(n / max(flush_dt, 1e-9))
    finally:
        tracing.set_sender(None)
        tracing.stop_flusher()
        if prev_sample is None:
            os.environ.pop("RTPU_TRACE_SAMPLE", None)
        else:
            os.environ["RTPU_TRACE_SAMPLE"] = prev_sample
        tracing.refresh()

    # ---- (b) serve closed-loop: tracing on vs off ----
    def closed_loop(fn, n_clients, dur):
        lat, errors = [], [0]
        lock = threading.Lock()
        stop = time.perf_counter() + dur

        def worker():
            local = []
            while time.perf_counter() < stop:
                t0 = time.perf_counter()
                try:
                    fn()
                except Exception:
                    with lock:
                        errors[0] += 1
                    continue
                local.append(time.perf_counter() - t0)
            with lock:
                lat.extend(local)

        threads = [threading.Thread(target=worker)
                   for _ in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if not lat:
            return {"rps": 0.0, "p50_ms": 0.0, "p99_ms": 0.0,
                    "errors": errors[0]}
        arr = np.asarray(lat)
        return {"rps": round(len(lat) / dur, 1),
                "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 2),
                "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 2),
                "errors": errors[0]}

    class TraceEcho:
        def __init__(self, service_s):
            self.service_s = service_s

        def __call__(self, x):
            time.sleep(self.service_s)
            return x

    # Interleaved A/B windows in ONE cluster: per-run RPS drifts ~8% on
    # the 1-core box, far above the 5% gate, so mode-per-cluster
    # comparisons measure thermal luck. Tracing is driver-gated
    # (unsampled/disabled requests carry no trace ctx, so the replica
    # does zero tracing work), which makes toggling RTPU_TRACING in the
    # driver between back-to-back windows a fair whole-path comparison.
    import ray_tpu
    from ray_tpu import serve
    prev = os.environ.get("RTPU_TRACING")
    os.environ.pop("RTPU_TRACING", None)  # replicas: library default
    tracing.refresh()
    rounds = int(os.environ.get("BENCH_TRACE_ROUNDS", 3))
    ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024,
                 _system_config={"prestart_workers": False})
    try:
        from ray_tpu.serve.handle import _reset_router
        _reset_router()
        h = serve.run(
            serve.deployment(num_replicas=2,
                             max_concurrent_queries=32)(
                TraceEcho).bind(service_ms / 1e3),
            name="trace_bench", http_port=None)
        seq = iter(range(1 << 30))

        def call():
            import ray_tpu as rt
            rt.get(h.remote(
                1, __rtpu_request_id__=f"tb-{next(seq)}"),
                timeout=30.0)

        for _ in range(16):
            call()  # warm replicas + router + span path
        stats = {"off": [], "on": []}
        for _ in range(rounds):
            for mode, env in (("off", "0"), ("on", "1")):
                os.environ["RTPU_TRACING"] = env
                tracing.refresh()
                stats[mode].append(closed_loop(call, clients, duration))
        for mode in ("off", "on"):
            best = max(s["rps"] for s in stats[mode])
            out[f"serve_{mode}_rps"] = round(
                sum(s["rps"] for s in stats[mode]) / rounds, 1)
            out[f"serve_{mode}_rps_best"] = best
            out[f"serve_{mode}_p50_ms"] = round(
                sum(s["p50_ms"] for s in stats[mode]) / rounds, 2)
            out[f"serve_{mode}_p99_ms"] = round(
                max(s["p99_ms"] for s in stats[mode]), 2)
            out[f"serve_{mode}_errors"] = sum(
                s["errors"] for s in stats[mode])
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
        if prev is None:
            os.environ.pop("RTPU_TRACING", None)
        else:
            os.environ["RTPU_TRACING"] = prev
        tracing.refresh()
    if out.get("serve_off_rps"):
        out["trace_overhead_rps_ratio"] = round(
            out["serve_on_rps"] / out["serve_off_rps"], 3)
        out["trace_overhead_ok"] = \
            out["trace_overhead_rps_ratio"] >= 0.95
    print(json.dumps({"metric": "tracing", **out}), flush=True)


def main():
    if os.environ.get("_BENCH_RAW"):
        _raw_main()
    elif os.environ.get("_BENCH_DATA_INGEST"):
        try:
            _data_ingest_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_CKPT"):
        try:
            _ckpt_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_SERVE"):
        try:
            _serve_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_SERVE_HA"):
        try:
            _serve_ha_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_CHAOS"):
        try:
            _chaos_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_STATE"):
        try:
            _state_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_DAG"):
        try:
            _dag_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_NET"):
        try:
            _net_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_TRACE"):
        try:
            _trace_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_GAMEDAY"):
        try:
            _gameday_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_LLM"):
        try:
            _llm_bench_main()
        except Exception as e:  # noqa: BLE001 — supervisor parses output
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
    elif os.environ.get("_BENCH_FRAMEWORK"):
        print(json.dumps(_framework_main()), flush=True)
    else:
        _supervise()


if __name__ == "__main__":
    main()
