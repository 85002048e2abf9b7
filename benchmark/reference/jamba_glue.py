"""Weights of a Jamba configuration from ``--seed``, made on the device in
the program's own tree and stored as the program stores them (the
reference reads the same arrays and lifts them itself), and the
program's config object from the configuration file.

Initialisers (the configuration file lists them under ``assumed``): every
matrix and the token table N(0, 0.02); norm gains 1 + N(0, 0.02); the
depthwise convolution's taps N(0, 0.5) and its bias N(0, 0.02); ``A_log``
= log(1 .. N) a channel and ``D`` = 1 (the family's own); ``dt_bias`` =
softplus^-1 of a step log-uniform in [0.001, 0.1] (the family's own:
per-token decays exp(-16 * 0.1) .. exp(-0.001), so some states forget in
a few tokens and some keep a thousand)."""

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
        k = jax.random.fold_in(key, n)
        if name == "scale":
            x = 1.0 + 0.02 * jax.random.normal(k, sds.shape, f32)
        elif name == "A_log":       # [.., N, d_in]: log(n + 1) a channel
            x = jnp.broadcast_to(jnp.log(jnp.arange(
                1, sds.shape[-2] + 1, dtype=f32))[:, None], sds.shape)
        elif name == "D":
            x = jnp.ones(sds.shape, f32)
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, sds.shape, f32, jnp.log(0.001), jnp.log(0.1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        else:
            std = 0.5 if name == "conv" else 0.02
            x = std * jax.random.normal(k, sds.shape, f32)
        out.append(x.astype(sds.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def init_for(cfg, seed: int):
    """The program's parameter tree (``{"params": ...}``) from the seed,
    in one jitted call; ``seed`` may be any whole number."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.jamba import JambaModel
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    shapes = jax.eval_shape(JambaModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return jax.jit(functools.partial(_init, shapes=shapes))(key)
