#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile, for a v5e that
is described and not attached, every program the cells will run at their
real sizes, and read ``memory_analysis()``. Nothing runs, so this says
nothing about results or times; it says what the chip's compiler refuses
and what each program needs beside the weights and the pool.

    JAX_PLATFORMS=cpu python benchmark/checks/compile_real_size.py \\
        [--config gpt2_large] [--traffic serve_closed32] [--only B,S ...]
    JAX_PLATFORMS=cpu python benchmark/checks/compile_real_size.py \\
        --config gpt2_small

A builder's tool: the benchmark's runs never call it. It reaches into
``FlaxModelAdapter._step_fn`` (the jitted program itself) because a
described device cannot hold the arrays the public ``prefill``/``decode``
would build.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GIB = 1024.0 ** 3


def _report(what, compiled, resident_bytes, t0):
    ma = compiled.memory_analysis()
    temp, arg = ma.temp_size_in_bytes, ma.argument_size_in_bytes
    out, alias = ma.output_size_in_bytes, ma.alias_size_in_bytes
    need = arg + out - alias + temp
    print(f"{what}: temp {temp / GIB:.3f} GiB, arguments {arg / GIB:.3f}, "
          f"outputs {out / GIB:.3f}, aliased {alias / GIB:.3f} -> program "
          f"needs {need / GIB:.3f} GiB (resident beside it "
          f"{resident_bytes / GIB:.3f} GiB); compiled in "
          f"{time.time() - t0:.0f}s", flush=True)
    return need


def serve(cfg, traffic, only, one_chip, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import buckets
    from benchmark.reference import gpt2_glue
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter

    engine = cfg["serve"]["engine"]
    mcfg = gpt2_glue.model_config(cfg["model"])
    adapter = FlaxModelAdapter(kind=cfg["serve"]["model"], config=mcfg,
                               params={})
    params = jax.eval_shape(
        lambda: adapter.model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 8), jnp.int32)))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(params)
    nb_max = -(-mcfg.n_positions // engine["block_size"])
    pool = jax.ShapeDtypeStruct(
        (mcfg.n_layer, engine["num_blocks"], engine["block_size"],
         mcfg.n_head, mcfg.n_embd // mcfg.n_head), mcfg.dtype,
        sharding=one_chip)
    weight_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                       for s in jax.tree_util.tree_leaves(params))
    pool_bytes = 2 * int(np.prod(pool.shape)) * pool.dtype.itemsize
    print(f"weights {weight_bytes / GIB:.3f} GiB, KV pool "
          f"{pool_bytes / GIB:.3f} GiB", flush=True)
    args = (traffic["prompt_len"]["min"], traffic["prompt_len"]["max"],
            engine["max_prefill_tokens"], engine["max_running"])
    shapes = sorted(buckets.prefill_buckets(*args)) + [
        (b, 8) for b in buckets.decode_buckets(engine["max_running"])]
    if only:
        shapes = [s for s in shapes if s in only]
    # the adapter donates the pools when its first device is a TPU
    real_devices = jax.devices
    worst = 0
    for B, S in shapes:
        jax.devices = lambda *a, **k: topo.devices
        try:
            fn = adapter._step_fn(B, S)
        finally:
            jax.devices = real_devices

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        t0 = time.time()
        compiled = fn.lower(
            params, sds((B, S), jnp.int32), pool, pool,
            sds((B, nb_max), jnp.int32), sds((B,), jnp.int32),
            sds((B, S), jnp.bool_)).compile()
        need = _report(f"serve step (B={B}, S={S})", compiled, 0, t0)
        worst = max(worst, need)
    print(f"largest program needs {worst / GIB:.3f} GiB of the chip's "
          f"{16e9 / GIB:.3f} GiB (weights and pool are among its "
          "arguments)", flush=True)


def train(cfg, one_chip, topo):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import gpt2_glue
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.spmd import make_causal_lm_trainer

    attention._use_pallas = lambda: True      # the chip's branch
    mcfg = gpt2_glue.model_config(cfg["model"])
    spec = MeshSpec()
    mesh = spec.build(topo.devices[:1])
    trainer = make_causal_lm_trainer(mcfg, mesh=mesh, spec=spec)
    state = jax.eval_shape(trainer.init, jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, trainer.state_sharding_tree)
    b, s = cfg["train"]["batch_size"], cfg["train"]["seq_len"]
    batch = {k: jax.ShapeDtypeStruct((b, s), jnp.int32,
                                     sharding=trainer.batch_shardings[k])
             for k in ("input_ids", "labels")}
    t0 = time.time()
    compiled = trainer.step.lower(state, batch).compile()
    _report(f"train step (b{b} x s{s})", compiled, 0, t0)
    text = compiled.as_text()
    print(f"Mosaic kernels in the step: {text.count('tpu_custom_call')}",
          flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="gpt2_large")
    ap.add_argument("--traffic", default="serve_closed32")
    ap.add_argument("--only", nargs="*", default=[],
                    help="B,S pairs, e.g. 32,8 1,1024")
    ap.add_argument("--max-running", type=int, default=0,
                    help="try another max_running (the pool follows: "
                         "one full-length sequence each + the null page)")
    a = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import cells
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = cells.load_json(os.path.join(cells.BENCH_DIR, "configs",
                                       a.config + ".json"))
    if cfg["runner"] == "serve_llm":
        traffic = cells.load_json(os.path.join(
            cells.BENCH_DIR, "traffic", a.traffic + ".json"))
        only = [tuple(int(x) for x in p.split(",")) for p in a.only]
        if a.max_running:
            engine = cfg["serve"]["engine"]
            engine["max_running"] = a.max_running
            engine["num_blocks"] = a.max_running * (
                engine["max_seq_len"] // engine["block_size"]) + 1
        serve(cfg, traffic, only, one_chip, topo)
    else:
        train(cfg, one_chip, topo)


if __name__ == "__main__":
    main()
