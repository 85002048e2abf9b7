"""The one place that knows both the reference's parameter names and the
program's: the benchmark makes the weights (``gpt2_ref.init_params``)
and hands the same arrays to the program under flax's names."""

from __future__ import annotations

import importlib


def to_flax_tree(p):
    def dense(x):
        return {"kernel": x["w"], "bias": x["b"]}

    def ln(x):
        return {"scale": x["g"], "bias": x["b"]}

    tree = {"wte": {"embedding": p["wte"]}, "wpe": {"embedding": p["wpe"]},
            "ln_f": ln(p["ln_f"])}
    for i, h in enumerate(p["h"]):
        tree[f"h_{i}"] = {
            "ln_1": ln(h["ln_1"]), "ln_2": ln(h["ln_2"]),
            "attn": {"c_attn": dense(h["attn"]), "c_proj": dense(h["proj"])},
            "mlp": {"c_fc": dense(h["fc"]), "c_proj": dense(h["fc_proj"])}}
    return tree


def model_config(model: dict, rehearse_kwargs=None):
    """The program's config object from the configuration file's
    ``model``: ``factory`` is ``module:Class``, ``kwargs`` its arguments
    (``dtype`` by name)."""
    import jax.numpy as jnp
    mod, cls = model["factory"].split(":")
    kwargs = dict(rehearse_kwargs or model["kwargs"])
    if isinstance(kwargs.get("dtype"), str):
        kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    return getattr(importlib.import_module(mod), cls)(**kwargs)


def init_for(cfg, seed: int):
    """Reference-named weights for the program's config, from the seed."""
    import jax.numpy as jnp
    from benchmark.reference import gpt2_ref
    return gpt2_ref.init_params(
        seed, n_layer=cfg.n_layer, n_embd=cfg.n_embd,
        vocab_size=cfg.vocab_size, n_positions=cfg.n_positions,
        dtype=jnp.float32)
