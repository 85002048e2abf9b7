"""Profile the two bench steps (ResNet-50, GPT-2) on the real chip with
jax.profiler and print a per-op time breakdown — the xplane-driven
tuning loop the round-4 verdict asked for (VERDICT r4 "Next round" #1).

Usage: python bench_profile.py [resnet|gpt2|both] [--trace-dir DIR]
Run it directly on the TPU (not under tests' CPU pin).
"""

from __future__ import annotations

import collections
import json
import os
import sys
import time


def _profile_model(which: str, trace_dir: str):
    import jax
    import numpy as np

    import jax.numpy as jnp
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.spmd import (make_causal_lm_trainer,
                                    make_image_classifier_trainer, put_batch)

    devices = jax.devices()
    n_dev = jax.local_device_count()
    spec = MeshSpec(dp=n_dev)
    mesh = spec.build(devices[:n_dev])

    if which == "resnet":
        from ray_tpu.models.resnet import create_resnet
        batch = 256 * n_dev
        model = create_resnet("resnet50", num_classes=1000,
                              dtype=jnp.bfloat16)
        trainer = make_image_classifier_trainer(
            model, mesh=mesh, spec=spec, input_shape=(1, 224, 224, 3))
        state = trainer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        images = rng.standard_normal((batch, 224, 224, 3), dtype=np.float32)
        labels = rng.integers(0, 1000, (batch,), dtype=np.int32)
        resident = put_batch(trainer, {"image": images, "label": labels})
    else:
        from ray_tpu.models.gpt2 import GPT2Config
        cfg = GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                         n_layer=12, n_head=12,
                         attention_backend="flash", dtype=jnp.bfloat16)
        batch = 16 * n_dev
        trainer = make_causal_lm_trainer(cfg, mesh=mesh, spec=spec)
        state = trainer.init(jax.random.PRNGKey(0))
        tokens = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch, 1024), dtype=np.int32)
        resident = put_batch(trainer, {"input_ids": tokens,
                                       "labels": tokens})

    step = trainer.step.lower(state, resident).compile()
    for _ in range(3):
        state, metrics = step(state, resident)
    jax.block_until_ready(metrics["loss"])

    run_dir = os.path.join(trace_dir, which)
    with jax.profiler.trace(run_dir):
        for _ in range(5):
            state, metrics = step(state, resident)
        jax.block_until_ready(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(10):
        state, metrics = step(state, resident)
    jax.block_until_ready(metrics["loss"])
    dt = (time.perf_counter() - t0) / 10
    return run_dir, dt


def summarize(run_dir: str, top: int = 30):
    """Aggregate device-lane op durations from the chrome trace."""
    from ray_tpu.util.tpu_profiler import load_chrome_events

    events = load_chrome_events(run_dir)
    # device lanes: pid/tid names carrying "TPU" / XLA op events have
    # 'dur' and names like fusion.N, copy.N, etc.
    by_name = collections.Counter()
    counts = collections.Counter()
    meta_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            meta_names[(e.get("pid"), e.get("tid"))] = (
                e.get("args", {}).get("name", ""))
    device_tids = {k for k, v in meta_names.items()
                   if "XLA Op" in v or "Steps" in v or "TensorFlow Op" in v}
    for e in events:
        if e.get("ph") != "X":
            continue
        lane = meta_names.get((e.get("pid"), e.get("tid")), "")
        if not ("XLA Op" in lane or "TensorFlow Op" in lane):
            continue
        name = e.get("name", "?")
        by_name[name] += e.get("dur", 0)
        counts[name] += 1
    total = sum(by_name.values())
    rows = []
    for name, dur in by_name.most_common(top):
        rows.append({"op": name[:90], "us": dur, "n": counts[name],
                     "pct": round(100 * dur / max(total, 1), 1)})
    return {"total_us": total, "lanes": sorted(
        {v for v in meta_names.values() if v}), "rows": rows}


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    trace_dir = os.environ.get("BENCH_TRACE_DIR", "/tmp/bench_profile")
    models = ["resnet", "gpt2"] if which == "both" else [which]
    for m in models:
        run_dir, dt = _profile_model(m, trace_dir)
        print(f"\n=== {m}: step {dt * 1e3:.2f} ms ===")
        s = summarize(run_dir)
        print(f"lanes: {s['lanes'][:8]}")
        print(f"device total {s['total_us'] / 1e3:.1f} ms over trace")
        for r in s["rows"]:
            print(f"  {r['pct']:5.1f}%  {r['us'] / 1e3:9.2f} ms  n={r['n']:<4d} {r['op']}")


if __name__ == "__main__":
    main()
