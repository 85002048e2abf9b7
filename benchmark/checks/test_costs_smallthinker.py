"""The SmallThinker cost functions against numbers worked by hand (ISSUE
43's arithmetic), against the program's own parameter tree, and against a
brute-force count of a decode step's bytes at a tiny size."""

import os

import pytest

from benchmark.harness import cells, costs, costs_smallthinker as cs

CFG = cells.load_json(os.path.join(
    cells.BENCH_DIR, "configs", "smallthinker_21b_a3b.json"))["model"]["kwargs"]
# a case small enough for mental arithmetic: a full and two window layers
# (the layouts longer than the layers held, as the file's are), width 4,
# 4 query heads of 2 over 2 key/value heads, 6 experts of width 3, a
# window of 3
SMALL = dict(hidden_size=4, num_hidden_layers=3, head_dim=2,
             num_attention_heads=4, num_key_value_heads=2,
             sliding_window_layout=[0, 1, 1, 1, 0], sliding_window_size=3,
             moe_ffn_hidden_size=3, moe_num_primary_experts=6,
             moe_num_active_primary_experts=2, vocab_size=10)


def test_parts_are_the_issues_arithmetic():
    # 2560 x 3584 + 2 x 2560 x 512 + 3584 x 2560
    assert cs.attn_params(CFG) == 20_971_520
    assert cs.expert_params(CFG) == 3 * 2560 * 768 == 5_898_240
    assert cs.router_params(CFG) == 8 * 163_840
    assert cs.head_params(CFG) == 2560 * 151_936 == 388_956_160
    assert cs.layers_of(CFG, cs.FULL) == [0, 4]
    assert cs.layers_of(CFG, cs.WINDOW) == [1, 2, 3, 5, 6, 7]
    # a layer: 398.6 M; the issue's table: 3,966.9 M, 7.93 GB
    assert cs.attn_params(CFG) + 163_840 + 64 * cs.expert_params(CFG) \
        == 398_622_720
    assert cs.param_count(CFG) == 8 * 398_622_720 + 777_912_320
    assert round(cs.param_count(CFG) / 1e6, 1) == 3966.9
    # a cached token: 4 x 128 x 2 (K, V) x 2 B a layer; a page of 16
    assert cs.kv_row_bytes(CFG) == 2048 and 16 * cs.kv_row_bytes(CFG) == 32768
    # the pools of the configuration's engine, as the issue reckons them
    engine = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "smallthinker_21b_a3b.json"))[
            "serve"]["engine"]
    assert 2 * engine["num_blocks"] * 32768 == 1_610_678_272       # 1.50 GiB
    assert 6 * engine["window_blocks"] * 32768 == 3_624_075_264    # 3.375 GiB
    assert engine["window_blocks"] == 96 * 192 + 1 < 96 * 257 + 1


def test_small_case_by_hand():
    # attention 4x8 + 2x4x4 + 8x4 = 96; an expert 3x4x3 = 36
    assert cs.attn_params(SMALL) == 96 and cs.expert_params(SMALL) == 36
    assert cs.always_multiplied_params(SMALL) == 3 * 96
    assert cs.router_params(SMALL) == 3 * 24 and cs.head_params(SMALL) == 40
    assert cs.param_count(SMALL) == 288 + 72 + 3 * 6 * 36 + 80
    assert cs.kv_row_bytes(SMALL) == 16
    assert cs.layers_of(SMALL, cs.FULL) == [0]
    assert cs.layers_of(SMALL, cs.WINDOW) == [1, 2]
    # three rows of 10, 20 and 2 tokens: 32 live, the windows hold 3 + 3 +
    # 2 = 8; 5 experts touched by 6 pairs
    moe = cs.moe_experts_cost(SMALL, 5, 6)
    assert moe == {"bytes": 5 * 36 * 2 + 6 * 2 * 4 * 2, "flops": 2 * 6 * 36}
    full = cs.attend_cost(SMALL, cs.FULL, 3, 32)
    assert full == {"bytes": 32 * 16 + 4 * 3 * 2 * 2 * 2,
                    "flops": 4 * 2 * 4 * 32}
    window = cs.attend_cost(SMALL, cs.WINDOW, 3, 8)
    assert window == {"bytes": 2 * 8 * 16 + 2 * 4 * 3 * 2 * 2 * 2,
                      "flops": 4 * 2 * 2 * 4 * 8}
    step = cs.decode_step_cost(SMALL, 3, 32, 8, 5, 6)
    assert step["bytes"] == (288 + 40) * 2 + 72 * 4 + moe["bytes"] \
        + full["bytes"] + window["bytes"]
    assert step["flops"] == 2 * (288 + 40 + 72) * 3 + moe["flops"] \
        + full["flops"] + window["flops"]
    # a prompt of 5 tokens: the full layer's 15 pairs, each window
    # layer's 3 x 4 / 2 + 2 x 3 = 12 pairs, 4 heads
    assert cs.prefill_attention_flops(SMALL, 5) \
        == 4 * 2 * 4 * (15 + 2 * 12)
    assert cs.prefill_flops(SMALL, 5, 10) == 2 * (288 + 72) * 5 \
        + 2 * 10 * 36 + 2 * 40 + 4 * 2 * 4 * (15 + 2 * 12)
    # under the window both kinds count the whole triangle
    assert cs.prefill_attention_flops(SMALL, 2) == 4 * 2 * 4 * 3 * 3


def test_a_decode_step_of_the_cell_by_the_issues_reckoning():
    """96 rows whose contexts add up to 96 x 2,740 tokens (a mean prompt
    of 2,292 and half the mean 888 outputs) and whose windows hold 96 x
    1,900: every expert touched (96 x 6 pairs over 64): the issue's 8 x
    755 MB of experts, ~3.8 GB of K and V, a head of 0.78 GB."""
    live, held = 96 * 2740, 96 * 1900
    step = cs.decode_step_cost(CFG, 96, live, held, 8 * 64, 8 * 96 * 6)
    moe = cs.moe_experts_cost(CFG, 8 * 64, 8 * 96 * 6)
    assert 6.0e9 < moe["bytes"] < 6.1e9             # 8 x 755 MB
    kv = cs.attend_cost(CFG, cs.FULL, 96, live)["bytes"] \
        + cs.attend_cost(CFG, cs.WINDOW, 96, held)["bytes"]
    assert 3.2e9 < kv < 3.5e9
    assert 10.3e9 < step["bytes"] < 10.9e9
    peaks = cells.peaks_for("TPU v5 lite")
    least = costs.roofline_least_seconds(step["flops"], step["bytes"],
                                         peaks)
    assert least["bound"] == "memory" and 0.0125 < least["seconds"] < 0.0135
    # a prompt of 8,192 tokens, every token through 6 experts of 8 layers
    flops = cs.prefill_flops(CFG, 8192, 8192 * 6 * 8)
    attn = cs.prefill_attention_flops(CFG, 8192)
    # (218.1 M pairs x 4 x 128 x 28 heads = 3.13 TFLOP of attention; 2.77
    # through the attention's weights, 4.64 through the experts)
    assert 3.1e12 < attn < 3.15e12 and 10.4e12 < flops < 10.7e12


def test_the_programs_tree_has_the_counted_parameters():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from benchmark.reference import smallthinker_glue as glue
    from ray_tpu.models.smallthinker import SmallThinkerModel
    cfg = glue.model_config({
        "factory": "ray_tpu.models.smallthinker:SmallThinkerConfig",
        "kwargs": CFG})
    shapes = jax.eval_shape(SmallThinkerModel(cfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e6, 1) == 3966.9
    # norm gains (17 x 2560) and the selection biases (8 x 64) apart
    assert n - cs.param_count(CFG) == 17 * 2560 + 8 * 64


def test_a_steps_bytes_by_brute_force_at_a_tiny_size():
    """The tiny preset's own tree, leaf by leaf, and every row's reads,
    sequence by sequence and layer by layer, against ``decode_step_cost``."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.smallthinker import (SmallThinkerConfig,
                                             SmallThinkerModel)
    cfg = SmallThinkerConfig.tiny(dtype=jnp.bfloat16)
    c = {k: getattr(cfg, k) for k in (
        "hidden_size", "num_hidden_layers", "head_dim",
        "num_attention_heads", "num_key_value_heads",
        "sliding_window_layout", "sliding_window_size",
        "moe_ffn_hidden_size", "moe_num_primary_experts",
        "moe_num_active_primary_experts", "vocab_size")}
    tree = jax.eval_shape(SmallThinkerModel(cfg).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    lengths = [3, 31, 32, 33, 200]          # the window is 32
    rng = np.random.default_rng(0)
    touched = rng.integers(1, 17, cfg.num_hidden_layers)   # a layer's
    pairs = len(lengths) * cfg.moe_num_active_primary_experts \
        * cfg.num_hidden_layers
    total = tree["lm_head"].size * 2
    for i in range(cfg.num_hidden_layers):
        layer = tree[f"layers_{i}"]
        total += sum(x.size for x in jax.tree_util.tree_leaves(
            layer["attn"])) * 2
        total += layer["moe"]["router"].size * 4
        total += int(touched[i]) * sum(
            layer["moe"][k].size // cfg.moe_num_primary_experts
            for k in ("w_gate", "w_up", "w_down")) * 2
        row = 2 * cfg.num_key_value_heads * cfg.head_dim * 2
        for n in lengths:
            read = min(n, cfg.sliding_window_size) \
                if cfg.sliding_window_layout[i] else n
            total += read * row
            # the row's query in, its output out
            total += 2 * cfg.num_attention_heads * cfg.head_dim * 2
    total += pairs * 2 * cfg.hidden_size * 2    # an assignment's row in, out
    step = cs.decode_step_cost(
        c, len(lengths), sum(lengths),
        sum(min(n, cfg.sliding_window_size) for n in lengths),
        int(touched.sum()), pairs)
    assert step["bytes"] == total
