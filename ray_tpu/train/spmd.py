"""SPMD training-step builders: full jitted train step over a device mesh.

This is the TPU-native heart of the Train layer. The reference wraps the
user's torch model in DDP/FSDP inside each worker (reference:
train/torch/train_loop_utils.py:51 prepare_model, :91 FSDP) and lets NCCL
sync gradients. Here there is no wrapper: the *whole* train step — forward,
backward, optimizer update — is one XLA program jitted over a
`jax.sharding.Mesh`, with parameter/optimizer/data shardings derived from a
`MeshSpec` (dp/fsdp/tp/sp/...). XLA inserts the psum/all-gather/
reduce-scatter collectives over ICI; there is nothing like a process group
to manage.

Design notes (TPU-first):
  - state is a plain dict pytree {params, opt, step}: optax state mirrors
    the param tree, so one path-based sharding rule covers both.
  - `donate_argnums=(0,)` donates the state buffers — the update is
    in-place in HBM, no 2x parameter memory.
  - batch sharding: batch dim over (dp, fsdp), sequence dim over sp (ring
    attention consumes the seq shards).
  - loss/metrics come back replicated (XLA psums them across dp).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu._private import tracing
from ray_tpu.ops.attention import attention_mesh
from ray_tpu.parallel.mesh import MeshSpec, param_sharding


def state_shardings(abstract_state, mesh, spec: MeshSpec, override=None):
    """Sharding pytree for an arbitrary train-state pytree.

    Optax states (mu/nu of adam) mirror the param tree, so the trailing path
    keys hit the same `param_sharding` rules as the params themselves;
    scalars (step counts, schedules) replicate. `override(keys, shape)` may
    return a NamedSharding to take precedence for special leaves (e.g.
    stage-stacked pipeline params, expert-stacked MoE params).
    """
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    leaves, treedef = tree_flatten_with_path(abstract_state)
    out = []
    for path, leaf in leaves:
        keys = tuple(getattr(p, "key", getattr(p, "idx", str(p)))
                     for p in path)
        shape = getattr(leaf, "shape", ())
        special = override(keys, shape) if override is not None else None
        if special is not None:
            out.append(special)
        elif len(shape) == 0:
            out.append(NamedSharding(mesh, P()))
        else:
            out.append(param_sharding(mesh, keys, shape, spec))
    return tree_unflatten(treedef, out)


@dataclasses.dataclass
class SpmdTrainer:
    """A compiled SPMD training program bound to a mesh.

    init(rng) -> state                (sharded across the mesh)
    step(state, batch) -> state, metrics
    eval_loss(state, batch) -> loss   (optional; pipelined trainers attach a
                                       sequential pp=1 oracle here for
                                       parity checks)
    """
    mesh: Any
    spec: MeshSpec
    init: Callable
    step: Callable
    batch_shardings: Any
    state_sharding_tree: Any
    eval_loss: Optional[Callable] = None


@tracing.setup_span("train.setup.build")
def make_causal_lm_trainer(
    model_config=None,
    *,
    mesh=None,
    spec: Optional[MeshSpec] = None,
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
    model=None,
) -> SpmdTrainer:
    """GPT-style causal-LM SPMD trainer (the flagship train step).

    Reference analogue (capability, not design): the HF GPT-2 fine-tune
    config (train/huggingface/huggingface_trainer.py:157) — there, torch
    Trainer + DDP inside Ray workers; here, one pjit'd program over the mesh.

    What a worker does before its first step is recorded (docs/TRACING.md,
    "Before a process is ready"): this builder runs under
    ``train.setup.build`` and ``trainer.init`` under ``train.setup.init``
    (the call: the state's program traced, compiled and dispatched);
    ``trainer.step`` is the jitted function itself, and its first call's
    cost is its row of ``tracing.programs()``.
    """
    from ray_tpu.models.gpt2 import GPT2, GPT2Config, causal_lm_loss

    if spec is None:
        spec = MeshSpec()
    if mesh is None:
        mesh = spec.build()
    if model is None:
        model = GPT2(model_config or GPT2Config.small())

    tx = optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, b1=0.9, b2=0.95,
                    weight_decay=weight_decay),
    )

    seq_probe = 8  # init only traces shapes; seq length is free at step time

    def init_fn(rng):
        params = model.init(rng, jnp.zeros((1, seq_probe), jnp.int32))[
            "params"]
        return {"params": params, "opt": tx.init(params),
                "step": jnp.zeros((), jnp.int32)}

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    st_sh = state_shardings(abstract, mesh, spec)

    init = tracing.setup_span("train.setup.init")(
        jax.jit(init_fn, out_shardings=st_sh))

    batch_sh = {
        "input_ids": NamedSharding(mesh, P(("dp", "fsdp"), "sp")),
        "labels": NamedSharding(mesh, P(("dp", "fsdp"), "sp")),
    }
    repl = NamedSharding(mesh, P())

    dropout = float(getattr(model.config, "dropout", 0.0) or 0.0)
    base_rng = jax.random.PRNGKey(17)

    def train_step(state, batch):
        def loss_fn(p):
            if dropout > 0.0:
                logits = model.apply(
                    {"params": p}, batch["input_ids"], deterministic=False,
                    rngs={"dropout": jax.random.fold_in(
                        base_rng, state["step"])})
            else:
                logits = model.apply({"params": p}, batch["input_ids"],
                                     deterministic=True)
            return causal_lm_loss(logits, batch["labels"])

        with attention_mesh(mesh):
            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        with jax.named_scope("optimizer"):
            updates, opt = tx.update(grads, state["opt"], state["params"])
            params = optax.apply_updates(state["params"], updates)
            gnorm = optax.global_norm(grads)
        new_state = {"params": params, "opt": opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    step = jax.jit(
        train_step,
        in_shardings=(st_sh, batch_sh),
        out_shardings=(st_sh, {"loss": repl, "grad_norm": repl}),
        donate_argnums=(0,),
    )
    return SpmdTrainer(mesh=mesh, spec=spec, init=init, step=step,
                       batch_shardings=batch_sh, state_sharding_tree=st_sh)


def make_image_classifier_trainer(
    model,
    *,
    mesh=None,
    spec: Optional[MeshSpec] = None,
    learning_rate: float = 0.1,
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    input_shape: Tuple[int, ...] = (1, 224, 224, 3),
) -> SpmdTrainer:
    """ResNet-style SPMD trainer with batch-norm state.

    Reference analogue: resnet50_ray_air.py (MLPerf-style ResNet-50 DDP
    benchmark). State carries flax `batch_stats`; cross-dp batchnorm uses
    the local shard statistics (the standard large-batch approximation —
    the reference's torch DDP BatchNorm does the same).
    """
    if spec is None:
        spec = MeshSpec()
    if mesh is None:
        mesh = spec.build()

    tx = optax.chain(
        optax.add_decayed_weights(weight_decay),
        optax.sgd(learning_rate, momentum=momentum, nesterov=True),
    )

    def init_fn(rng):
        variables = model.init(rng, jnp.zeros(input_shape, jnp.float32),
                               train=False)
        params = variables["params"]
        return {"params": params,
                "batch_stats": variables.get("batch_stats", {}),
                "opt": tx.init(params),
                "step": jnp.zeros((), jnp.int32)}

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    st_sh = state_shardings(abstract, mesh, spec)
    init = jax.jit(init_fn, out_shardings=st_sh)

    batch_sh = {
        "image": NamedSharding(mesh, P(("dp", "fsdp"))),
        "label": NamedSharding(mesh, P(("dp", "fsdp"))),
    }
    repl = NamedSharding(mesh, P())

    def train_step(state, batch):
        img = batch["image"]
        if img.dtype == jnp.uint8:
            # uint8 input pipeline (MLPerf-style): ship bytes, normalize
            # on device — 4x less host->HBM traffic than f32 images
            img = img.astype(jnp.float32) / 127.5 - 1.0

        def loss_fn(p):
            out, mut = model.apply(
                {"params": p, "batch_stats": state["batch_stats"]},
                img, train=True, mutable=["batch_stats"])
            onehot = jax.nn.one_hot(batch["label"], out.shape[-1])
            loss = optax.softmax_cross_entropy(out, onehot).mean()
            return loss, (out, mut["batch_stats"])

        (loss, (logits, new_bs)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        with jax.named_scope("optimizer"):
            updates, opt = tx.update(grads, state["opt"], state["params"])
            params = optax.apply_updates(state["params"], updates)
        acc = jnp.mean(
            (jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
        new_state = {"params": params, "batch_stats": new_bs,
                     "opt": opt, "step": state["step"] + 1}
        return new_state, {"loss": loss, "accuracy": acc}

    step = jax.jit(
        train_step,
        in_shardings=(st_sh, batch_sh),
        out_shardings=(st_sh, {"loss": repl, "accuracy": repl}),
        donate_argnums=(0,),
    )
    return SpmdTrainer(mesh=mesh, spec=spec, init=init, step=step,
                       batch_shardings=batch_sh, state_sharding_tree=st_sh)


def make_pipelined_lm_trainer(
    model_config,
    *,
    mesh,
    spec: MeshSpec,
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
) -> SpmdTrainer:
    """Causal-LM trainer with PIPELINE parallelism over the ``pp`` axis.

    Structure: embed (computed outside the pipeline, replicated over pp) →
    stage-stacked transformer Blocks through the microbatched circular
    pipeline (parallel/pipeline.py: shard_map manual over pp, ppermute
    rotation, autodiff backward) → final-LN + untied head. dp shards the
    per-microbatch batch dim and tp/fsdp shard stage weights as usual —
    partial-manual shard_map leaves those axes to GSPMD.

    No reference analogue (the reference has no pipeline engine; SURVEY.md
    §2.6) — this is the TPU-native bar for PP.
    """
    import flax.linen as nn

    from ray_tpu.models.gpt2 import Block, causal_lm_loss
    from ray_tpu.parallel.pipeline import pipeline_apply

    cfg = model_config
    n_stages = spec.pp
    assert cfg.n_layer % n_stages == 0, \
        f"n_layer={cfg.n_layer} must divide into pp={n_stages} stages"
    layers_per_stage = cfg.n_layer // n_stages

    class Embed(nn.Module):
        @nn.compact
        def __call__(self, ids):
            pos = jnp.arange(ids.shape[-1])
            wte = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype,
                           name="wte")
            wpe = nn.Embed(cfg.n_positions, cfg.n_embd, dtype=cfg.dtype,
                           name="wpe")
            return wte(ids) + wpe(pos)

    class Stage(nn.Module):
        @nn.compact
        def __call__(self, x):
            for i in range(layers_per_stage):
                x = Block(cfg, name=f"h_{i}")(x, deterministic=True)
            return x

    class Head(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
            return nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                            name="lm_head")(x)

    embed_m, stage_m, head_m = Embed(), Stage(), Head()
    tx = optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, b1=0.9, b2=0.95,
                    weight_decay=weight_decay),
    )

    seq_probe = 8

    def init_fn(rng):
        r_e, r_s, r_h = jax.random.split(rng, 3)
        ids = jnp.zeros((1, seq_probe), jnp.int32)
        x = jnp.zeros((1, seq_probe, cfg.n_embd), cfg.dtype)
        stage_rngs = jax.random.split(r_s, n_stages)
        params = {
            "embed": embed_m.init(r_e, ids)["params"],
            "stages": jax.vmap(
                lambda r: stage_m.init(r, x)["params"])(stage_rngs),
            "head": head_m.init(r_h, x)["params"],
        }
        return {"params": params, "opt": tx.init(params),
                "step": jnp.zeros((), jnp.int32)}

    # shardings: stage-stacked leaves get P("pp", <usual tp/fsdp rule>);
    # embed/head replicate over pp (their tp/fsdp rules still apply)
    def _stage_override(keys, shape):
        if "stages" in keys and len(shape) >= 1:
            inner = param_sharding(mesh, keys, shape[1:], spec)
            return NamedSharding(mesh, P("pp", *inner.spec))
        return None

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    st_sh = state_shardings(abstract, mesh, spec, override=_stage_override)
    init = jax.jit(init_fn, out_shardings=st_sh)

    # batches arrive pre-microbatched: [M, mb, T]; dp shards mb, sp shards T
    batch_sh = {
        "input_ids": NamedSharding(mesh, P(None, ("dp", "fsdp"), "sp")),
        "labels": NamedSharding(mesh, P(None, ("dp", "fsdp"), "sp")),
    }
    repl = NamedSharding(mesh, P())
    piped = pipeline_apply(
        lambda p, x: stage_m.apply({"params": p}, x), mesh)

    def train_step(state, batch):
        def loss_fn(p):
            x = embed_m.apply({"params": p["embed"]}, batch["input_ids"])
            y = piped(p["stages"], x.astype(cfg.dtype))
            logits = head_m.apply({"params": p["head"]}, y)
            return causal_lm_loss(
                logits.reshape(-1, logits.shape[-2], logits.shape[-1]),
                batch["labels"].reshape(-1, batch["labels"].shape[-1]))

        loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        with jax.named_scope("optimizer"):
            updates, opt = tx.update(grads, state["opt"], state["params"])
            params = optax.apply_updates(state["params"], updates)
            gnorm = optax.global_norm(grads)
        new_state = {"params": params, "opt": opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    step = jax.jit(
        train_step,
        in_shardings=(st_sh, batch_sh),
        out_shardings=(st_sh, {"loss": repl, "grad_norm": repl}),
        donate_argnums=(0,),
    )

    def eval_loss_fn(state, batch):
        """pp=1 oracle: the same params through sequential_apply."""
        from ray_tpu.parallel.pipeline import sequential_apply
        p = state["params"]
        x = embed_m.apply({"params": p["embed"]}, batch["input_ids"])
        y = sequential_apply(
            lambda sp, xx: stage_m.apply({"params": sp}, xx),
            p["stages"], x.astype(cfg.dtype))
        logits = head_m.apply({"params": p["head"]}, y)
        return causal_lm_loss(
            logits.reshape(-1, logits.shape[-2], logits.shape[-1]),
            batch["labels"].reshape(-1, batch["labels"].shape[-1]))

    return SpmdTrainer(mesh=mesh, spec=spec, init=init, step=step,
                       batch_shardings=batch_sh, state_sharding_tree=st_sh,
                       eval_loss=jax.jit(eval_loss_fn))


def make_async_checkpointer(manager=None, **kwargs):
    """An AsyncCheckpointer for SPMD train state. With ``manager=None``
    inside a train session, binds to the run's durable checkpoint root
    (the driver commits after the gang round barrier); standalone callers
    pass their own CheckpointManager and get self-committing saves.

    Usage in a train_func::

        ckpter = spmd.make_async_checkpointer()
        ...
        pending = ckpter.save(session.next_checkpoint_step(), state)
        session.report(metrics, checkpoint=pending)   # blocks only for
        ...                                           # the host snapshot
        ckpter.finalize()                             # before returning
    """
    from ray_tpu.checkpoint import AsyncCheckpointer
    if manager is None:
        from ray_tpu.air import session as air_session
        ckpter = air_session.get_async_checkpointer()
        if ckpter is None:
            raise RuntimeError(
                "no checkpoint manager in the session — set "
                "RunConfig.name/storage_path, or pass manager= explicitly")
        return ckpter
    return AsyncCheckpointer(manager, **kwargs)


def restore_spmd_state(target_state, *, manager=None, checkpoint=None,
                       step: Optional[int] = None):
    """Restore a sharded checkpoint onto ``target_state``'s shardings.

    World-size/mesh independent: shards are keyed by *global* index
    slices, so a state saved by 8 processes on a (dp=4, tp=2) mesh
    reassembles onto 1 process with a (dp=2,) mesh (and vice versa) —
    each leaf is rebuilt full on host and ``device_put`` re-shards it to
    the target layout. Source: a CheckpointManager (committed step, with
    checksum verification under RTPU_CKPT_VERIFY=1), a directory-backed
    air.Checkpoint, or the session's manager."""
    from ray_tpu.air.checkpoint import ShardedCheckpoint
    if manager is None and checkpoint is None:
        from ray_tpu.air import session as air_session
        manager = air_session.get_checkpoint_manager()
        if manager is None:
            checkpoint = air_session.get_checkpoint()
    if manager is not None:
        return manager.restore_state(target_state, step=step)
    root = getattr(checkpoint, "_dir", None)
    if root is None:
        raise ValueError("restore_spmd_state needs a CheckpointManager or "
                         "a directory-backed Checkpoint")
    return ShardedCheckpoint(root).restore(target_state)


def put_batch(trainer: SpmdTrainer, batch: Dict[str, np.ndarray]):
    """Host batch -> sharded device arrays matching the trainer layout."""
    return {k: jax.device_put(v, trainer.batch_shardings[k])
            for k, v in batch.items()}


def default_spec_for(n_devices: int) -> MeshSpec:
    """A sensible multi-axis MeshSpec exercising dp/tp/sp for N devices.

    Used by the multichip dryrun: factorize N into (dp, sp, tp) with tp/sp
    innermost (ICI-adjacent), dp taking the remainder.
    """
    tp = 2 if n_devices % 2 == 0 else 1
    rem = n_devices // tp
    sp = 2 if rem % 2 == 0 and rem >= 4 else 1
    dp = rem // sp
    return MeshSpec(dp=dp, sp=sp, tp=tp)
