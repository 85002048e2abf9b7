"""The Laguna cost functions against numbers worked by hand (ISSUE 37's
arithmetic), on small hand-computed cases, and against the program's own
parameter tree."""

import os

import pytest

from benchmark.harness import cells, costs, costs_laguna as cl

CFG = cells.load_json(os.path.join(
    cells.BENCH_DIR, "configs", "laguna_xs_2.json"))["model"]["kwargs"]
# a case small enough for mental arithmetic: a full dense layer and a
# sliding sparse one, width 4, heads of 2 (2 and 4 query heads over 2
# key/value heads), widths 5, 3 and 3, 6 experts, a window of 3
SMALL = dict(hidden_size=4, num_hidden_layers=2, head_dim=2,
             num_key_value_heads=2, num_attention_heads_per_layer=[2, 4],
             layer_types=[cl.FULL, cl.SLIDING],
             mlp_layer_types=["dense", "sparse"], sliding_window=3,
             intermediate_size=5, moe_intermediate_size=3,
             shared_expert_intermediate_size=3, num_experts=6,
             num_experts_per_tok=2, vocab_size=10)


def test_parts_are_the_issues_arithmetic():
    # 2048 x 6144 + 2 x 2048 x 1024 + 2048 x 48 + 6144 x 2048
    assert cl.attn_params(CFG, 0) == cl.attn_params(CFG, 4) == 29458432
    assert round(cl.attn_params(CFG, 0) / 1e6, 2) == 29.46
    # 2048 x 8192 + 2 x 2048 x 1024 + 2048 x 64 + 8192 x 2048
    assert cl.attn_params(CFG, 1) == 37879808
    assert cl.expert_params(CFG) == 3 * 2048 * 512 == 3145728
    assert cl.dense_ffn_params(CFG) == 3 * 2048 * 8192 == 50331648
    assert cl.sparse_layers(CFG) == 4
    assert cl.router_params(CFG) == 4 * 2048 * 256
    assert cl.head_params(CFG) == 2048 * 100352 == 205520896
    # the issue's table: dense + 4, 3,869.8 M
    assert round(cl.param_count(CFG) / 1e6, 1) == 3869.8
    assert cl.kv_row_bytes(CFG) == 4096
    # a sparse layer: 256 experts, the shared one, the router: 808.98 M
    assert round((256 * cl.expert_params(CFG) + cl.shared_params(CFG)
                  + 2048 * 256) / 1e6, 2) == 808.98


def test_small_case_by_hand():
    # attention: full 4x4 + 2x4x4 + 4x2 + 4x4 = 72; sliding 4x8 + 32 + 16
    # + 8x4 = 112; expert 3x4x3 = 36; dense 3x4x5 = 60
    assert cl.attn_params(SMALL, 0) == 72 and cl.attn_params(SMALL, 1) == 112
    assert cl.expert_params(SMALL) == cl.shared_params(SMALL) == 36
    assert cl.always_multiplied_params(SMALL) == 72 + 112 + 60 + 36 == 280
    assert cl.router_params(SMALL) == 24 and cl.head_params(SMALL) == 40
    assert cl.param_count(SMALL) == 280 + 24 + 6 * 36 + 80
    assert cl.kv_row_bytes(SMALL) == 16
    # three rows of 10, 20 and 30 tokens: 60 live, the windows hold 9;
    # 4 experts touched by 6 pairs
    moe = cl.moe_experts_cost(SMALL, 4, 6)
    assert moe == {"bytes": 4 * 36 * 2 + 6 * 2 * 4 * 2, "flops": 2 * 6 * 36}
    full = cl.attend_cost(SMALL, cl.FULL, 3, 60)
    assert full == {"bytes": 60 * 16 + 2 * 3 * 2 * 2 * 2,
                    "flops": 4 * 2 * 2 * 60}
    window = cl.attend_cost(SMALL, cl.SLIDING, 3, 9)
    assert window == {"bytes": 9 * 16 + 4 * 3 * 2 * 2 * 2,
                      "flops": 4 * 2 * 4 * 9}
    step = cl.decode_step_cost(SMALL, 3, 60, 9, 4, 6)
    assert step["bytes"] == (280 + 40) * 2 + 24 * 4 + moe["bytes"] \
        + full["bytes"] + window["bytes"]
    assert step["flops"] == 2 * (280 + 40 + 24) * 3 + moe["flops"] \
        + full["flops"] + window["flops"]
    # a prompt of 5 tokens: the full layer's 15 pairs at 2 heads, the
    # sliding layer's 3 x 4 / 2 + 2 x 3 = 12 pairs at 4 heads
    assert cl.prefill_attention_flops(SMALL, 5) \
        == 4 * 2 * (15 * 2 + 12 * 4)
    assert cl.prefill_flops(SMALL, 5, 10) == 2 * (280 + 24) * 5 \
        + 2 * 10 * 36 + 2 * 40 + 4 * 2 * (15 * 2 + 12 * 4)
    # under the window both kinds count the whole triangle
    assert cl.prefill_attention_flops(SMALL, 2) == 4 * 2 * 3 * (2 + 4)


def test_a_decode_step_of_the_cell_by_the_issues_reckoning():
    """64 rows of 405 k live tokens, 220 experts touched a layer: the
    issue's ~10.2 GB and a least time of ~12.5 ms at 819 GB/s."""
    step = cl.decode_step_cost(CFG, 64, 405000, 64 * 512, 4 * 220, 4 * 512)
    assert 9.9e9 < step["bytes"] < 10.5e9
    peaks = cells.peaks_for("TPU v5 lite")
    least = costs.roofline_least_seconds(step["flops"], step["bytes"],
                                         peaks)
    assert least["bound"] == "memory" and 0.012 < least["seconds"] < 0.013
    # a prompt of 8,192 tokens, every token through 8 experts: the
    # issue's 7.6 TFLOP (5.5 of products, 1.65 full, 0.4 window)
    flops = cl.prefill_flops(CFG, 8192, 8192 * 8 * 4)
    assert 7.4e12 < flops < 7.8e12
    attn = cl.prefill_attention_flops(CFG, 8192)
    assert 2.0e12 < attn < 2.1e12


def test_the_programs_tree_has_the_counted_parameters():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from benchmark.reference import laguna_glue as glue
    from ray_tpu.models.laguna import LagunaModel
    cfg = glue.model_config({"factory": "ray_tpu.models.laguna:LagunaConfig",
                             "kwargs": CFG})
    shapes = jax.eval_shape(LagunaModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    # norm gains (11 x 2048) and the selection biases (4 x 256) apart
    assert n - cl.param_count(CFG) == 11 * 2048 + 4 * 256
