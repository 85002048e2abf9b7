"""Weights of a SmallThinker configuration from ``--seed``, made on the
device in the program's own tree and stored as the program stores them
(the reference reads the same arrays and lifts them itself), and the
program's config object from the configuration file:
``kimi_linear_glue.py``'s, for this model's tree.

Initialisers (the configuration file lists them under ``assumed``): every
matrix and the token table N(0, 0.02); norm gains 1 + N(0, 0.02); the
router N(0, 0.02) in float32; ``router_bias`` (a parameter of
``RoutedExperts`` that this family does not have) zeros, which leaves the
choice to the logits alone."""

from __future__ import annotations

import functools

from benchmark.reference.kimi_linear_glue import _init, \
    model_config  # noqa: F401


def init_for(cfg, seed: int):
    """The program's parameter tree (``{"params": ...}``) from the seed,
    in one jitted call; ``seed`` may be any whole number."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.smallthinker import SmallThinkerModel
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    shapes = jax.eval_shape(SmallThinkerModel(cfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return jax.jit(functools.partial(_init, shapes=shapes))(key)
