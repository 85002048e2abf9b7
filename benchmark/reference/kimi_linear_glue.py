"""Weights of a Kimi-Linear configuration from ``--seed``, made on the
device in the program's own tree and stored as the program stores them
(the reference reads the same arrays and lifts them itself), and the
program's config object from the configuration file.

Initialisers (the configuration file lists them under ``assumed``): every
matrix and the token table N(0, 0.02); norm gains 1 + N(0, 0.02); the
depthwise convolution taps N(0, 0.5); ``A_log`` = log U(1, 16);
``dt_bias`` = softplus^-1(U(0.001, 0.1)) (the usual initialisation of a
discretisation step: per-token decays exp(-16 * 0.1) .. exp(-0.001), so
some channels forget in a few tokens and some keep thousands); the
router N(0, 0.02) in float32, its correction bias zeros."""

from __future__ import annotations

import functools
import importlib


def model_config(model: dict, rehearse_kwargs=None):
    import jax.numpy as jnp
    mod, cls = model["factory"].split(":")
    kwargs = dict(rehearse_kwargs or model["kwargs"])
    if isinstance(kwargs.get("dtype"), str):
        kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    return getattr(importlib.import_module(mod), cls)(**kwargs)


def _init(key, shapes):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for n, (path, sds) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, n)
        if name == "scale":
            x = 1.0 + 0.02 * jax.random.normal(k, sds.shape, f32)
        elif name == "A_log":
            x = jnp.log(jax.random.uniform(k, sds.shape, f32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jax.random.uniform(k, sds.shape, f32, 0.001, 0.1)
            x = jnp.log(jnp.expm1(dt))
        elif name == "router_bias":
            x = jnp.zeros(sds.shape, f32)
        else:
            std = 0.5 if name == "qkv_conv" else 0.02
            x = std * jax.random.normal(k, sds.shape, f32)
        out.append(x.astype(sds.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def init_for(cfg, seed: int):
    """The program's parameter tree (``{"params": ...}``) from the seed,
    in one jitted call; ``seed`` may be any whole number."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.kimi_linear import KimiLinearModel
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    shapes = jax.eval_shape(KimiLinearModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return jax.jit(functools.partial(_init, shapes=shapes))(key)
