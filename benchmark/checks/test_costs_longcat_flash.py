"""The LongCat-Flash cost functions against numbers worked by hand (ISSUE
41's arithmetic), on a small hand-computed case, and against the
program's own parameter tree."""

import os

import pytest

from benchmark.harness import cells, costs, costs_longcat_flash as cl

CFG = cells.load_json(os.path.join(
    cells.BENCH_DIR, "configs",
    "longcat_flash_omni.json"))["model"]["kwargs"]
# a case small enough for mental arithmetic: 1 logical layer (two
# sublayers), width 4, one head of 2 + 1 and 2, ranks 3 and 2, widths 5
# and 3, 6 real experts and 2 zero-compute ones, 2 held
SMALL = dict(hidden_size=4, num_layers=1, num_attention_heads=1,
             q_lora_rank=3, kv_lora_rank=2, qk_nope_head_dim=2,
             qk_rope_head_dim=1, v_head_dim=2, ffn_hidden_size=5,
             expert_ffn_hidden_size=3, n_routed_experts=6,
             zero_expert_num=2, experts_held=[0, 2], moe_topk=2,
             vocab_size=10)


def test_parts_are_the_issues_arithmetic():
    # 6144x1536 + 1536x12288 + 6144x576 + 512x16384 + 8192x6144
    assert cl.mla_params(CFG) == 90570752
    assert cl.expert_params(CFG) == 3 * 6144 * 2048 == 37748736
    assert cl.dense_ffn_params(CFG) == 3 * 6144 * 12288 == 226492416
    assert cl.sublayers(CFG) == 8
    assert cl.router_params(CFG) == 4 * 6144 * 768
    assert cl.head_params(CFG) == 6144 * 16384 == 100663296
    # a layer without its experts: 2 MLA + 2 dense + router = 638.8 M;
    # 16 experts 604.0 M; the share held 5,172.6 M
    assert round((cl.always_multiplied_params(CFG)
                  + cl.router_params(CFG)) / 4 / 1e6, 1) == 638.8
    assert round(16 * cl.expert_params(CFG) / 1e6, 1) == 604.0
    assert round(cl.param_count(CFG) / 1e6, 1) == 5172.6
    assert cl.latent_row_bytes(CFG) == 1152


def test_small_case_by_hand():
    # attention: 4x3 + 3x3 + 4x3 + 2x4 + 2x4 = 49; expert 3x4x3 = 36;
    # dense 3x4x5 = 60; always = 2 x (49 + 60) = 218; router 4 x 8
    assert cl.mla_params(SMALL) == 49
    assert cl.expert_params(SMALL) == 36 and cl.dense_ffn_params(SMALL) == 60
    assert cl.always_multiplied_params(SMALL) == 218
    assert cl.router_params(SMALL) == 32 and cl.head_params(SMALL) == 40
    assert cl.param_count(SMALL) == 218 + 32 + 2 * 36 + 80
    # 2 touched experts, 3 real pairs (whatever went to a zero-compute
    # expert is in neither): 2 x 36 x 2 B + 3 x 2 x 4 x 2 B; 2 x 3 x 36
    assert cl.moe_experts_cost(SMALL, 2, 3) == {"bytes": 192.0,
                                                "flops": 216.0}
    # 2 sequences, 10 live tokens, 2 sublayers: rows 10 x 6 B and kv_b
    # 2x1x4 x 2 B a sublayer; per row 1 head x (2 + 2 + 1) MACs
    assert cl.mla_attend_cost(SMALL, 2, 10) == {
        "bytes": 2 * (60 + 16.0), "flops": 2.0 * 2 * (10 * 5 + 2 * 8)}
    step = cl.decode_step_cost(SMALL, 2, 10, 2, 3)
    assert step["bytes"] == (218 + 40) * 2 + 32 * 4 + 192 + 2 * 10 * 6
    assert step["flops"] == 2 * (218 + 40 + 32) * 2 + 216 + 2 * 2 * 10 * 5
    # a prompt of 3 tokens, 2 real pairs held: 2 x (218 + 32) x 3 + 2 x 2
    # x 36 + 2 x 40 + 2 sublayers x 1 head x 5 x 6 causal pairs x 2
    assert cl.prefill_flops(SMALL, 3, 2) == 1500 + 144 + 80 + 120
    assert cl.prefill_flops(SMALL, 3, 2, (4 + 16) / 2) \
        == cl.prefill_flops(SMALL, 3, 2) + 2 * 2 * 5 * 0.5


def test_param_count_is_the_programs_tree():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import longcat_flash_glue
    from ray_tpu.models.longcat_flash import LongcatFlashModel
    cfg = longcat_flash_glue.model_config({
        "factory": "ray_tpu.models.longcat_flash:LongcatFlashConfig",
        "kwargs": CFG})
    shapes = jax.eval_shape(LongcatFlashModel(cfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    matrices = sum(x.size for x in jax.tree_util.tree_leaves(shapes)
                   if x.ndim >= 2)
    assert cl.param_count(CFG) == matrices


def test_a_decode_step_of_the_cell_is_bound_by_memory():
    """64 rows of 2,200 live tokens, 40 touched (expert, layer) pairs, 64
    real pairs: the issue's reckoning (5.1 GB of dense sublayers, ~3 GB
    of experts, ~1.3 GB of latent rows, least ~11-12 ms)."""
    peaks = cells.peaks_for("TPU v5 lite")
    need = cl.decode_step_cost(CFG, 64, 64 * 2200, 40, 64)
    assert 9e9 < need["bytes"] < 10.5e9
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         peaks)
    assert least["bound"] == "memory" and 0.011 < least["seconds"] < 0.013
    attend = cl.mla_attend_cost(CFG, 64, 64 * 2200)
    assert attend["bytes"] == pytest.approx(8 * (140800 * 1152
                                                 + 512 * 64 * 256 * 2))
    # a prompt of 1,500 tokens with 500 real pairs a layer held: the dense
    # products dominate (the issue's 11.5 TFLOP is the 2,048 bucket's)
    flops = cl.prefill_flops(CFG, 1500, 4 * 500)
    assert 7.5e12 < flops < 8.5e12
    assert 11e12 < cl.prefill_flops(CFG, 2048, 4 * 683) < 12e12
