#!/usr/bin/env python3
"""The four-chip check: what exists only across chips, once, on the TPU
host, through the entry points a user calls. It measures nothing: every
time it prints is a smoke timing, not a benchmark. One chip is
``benchmark/run.py``'s ground (BENCHMARK.json's cells, each with a
``correct`` of its own); this script stays until a four-chip cell is
accepted there, and then it goes too.

    python chip_smoke.py    isolation (a chip belongs to one process: two
                            one-chip workers alive at once, each on its
                            own chip), then a GPT-2 small step on the
                            dp2 x tp2 mesh against the same seed and
                            batch on one device

The last line of stdout is ``{"ok": true, "device": {...}}`` with the
device as the TPU worker saw it. Any failed check, any phase on a
non-TPU device, or fewer than four chips: non-zero exit and no such line.

Process layout: a chip belongs to one process at a time. This driver
never initialises a jax backend (asserted at the end); every phase runs
in a worker process that owns its chips and must have exited, the chips'
device files closed, before the next phase starts (``wait_chip_free``
looks, it does not sleep and hope).

``--rehearse`` runs the same control flow at tiny sizes on the CPU, under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. A rehearsal
never prints the ok line and exits 3 when it passes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SEED = 20260926
MESH_TOL = 3e-2      # |dp2 x tp2 - one device| loss per step, bf16


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
        return dict(batch=2, seq=64)
    return dict(batch=16, seq=1024)


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
            tpus = int(ray_tpu.cluster_resources().get("TPU", 0))
            log(f"cluster resources: {ray_tpu.cluster_resources()}")
            check(rehearse or tpus == 4,
                  f"chip discovery found {tpus} TPU chip(s), this run "
                  "needs 4")
            for phase in (phase_isolation, phase_four):
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
