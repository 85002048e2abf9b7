"""Weights of an LFM2-MoE configuration from ``--seed``, made on the
device in the program's own tree and stored as the program stores them
(the reference reads the same arrays and lifts them itself), and the
program's config object from the configuration file.

Initialisers (the configuration file lists them under ``assumed``): every
matrix and the token table N(0, 0.02); norm gains 1 + N(0, 0.02) (the
operators', the feed-forward parts', the heads' query/key gains, the last
norm); the depthwise convolution's taps N(0, 0.5) (the size a depthwise
convolution's usual uniform(+-1/sqrt(K)) taps have: at 0.02 the operator
would add a thousandth of the residual and no check would see it); the
router N(0, 0.02) in float32; the expert bias (``router_bias``) N(0, 0.1)
in float32: DRAWN, because a zero bias cannot tell a program that weighs
by ``s + b`` from one that weighs by ``s``."""

from __future__ import annotations

import functools

from benchmark.reference.kimi_linear_glue import model_config  # noqa: F401


def _init(key, shapes):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for n, (path, sds) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        x = jax.random.normal(jax.random.fold_in(key, n), sds.shape, f32)
        x = 1.0 + 0.02 * x if name == "scale" else x * {
            "conv": 0.5, "router_bias": 0.1}.get(name, 0.02)
        out.append(x.astype(sds.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def init_for(cfg, seed: int):
    """The program's parameter tree (``{"params": ...}``) from the seed,
    in one jitted call; ``seed`` may be any whole number."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.lfm2 import Lfm2Model
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    shapes = jax.eval_shape(Lfm2Model(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return jax.jit(functools.partial(_init, shapes=shapes))(key)
