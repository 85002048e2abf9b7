"""The Kimi-K2 cost functions against numbers worked by hand (ISSUE 35's
arithmetic), on small hand-computed cases, and against the program's own
parameter tree."""

import os

import pytest

from benchmark.harness import cells, costs, costs_kimi_k2 as ck

CFG = cells.load_json(os.path.join(
    cells.BENCH_DIR, "configs", "kimi_k2_7_code.json"))["model"]["kwargs"]
# a case small enough for mental arithmetic: 2 layers (one dense), width
# 4, one head of 2 + 1 and 2, ranks 3 and 2, widths 5 and 3, 6 experts
SMALL = dict(hidden_size=4, num_hidden_layers=2, num_attention_heads=1,
             q_lora_rank=3, kv_lora_rank=2, qk_nope_head_dim=2,
             qk_rope_head_dim=1, v_head_dim=2, intermediate_size=5,
             first_k_dense_replace=1, moe_intermediate_size=3,
             n_routed_experts=6, experts_held=[0, 2], num_experts_per_tok=2,
             n_shared_experts=1, vocab_size=10)


def test_parts_are_the_issues_arithmetic():
    # 7168x1536 + 1536x12288 + 7168x576 + 512x16384 + 8192x7168
    assert ck.mla_params(CFG) == 101122048
    assert ck.expert_params(CFG) == 3 * 7168 * 2048 == 44040192
    assert ck.dense_ffn_params(CFG) == 3 * 7168 * 18432 == 396361728
    assert ck.routed_layers(CFG) == 6
    assert ck.router_params(CFG) == 6 * 7168 * 384
    assert ck.head_params(CFG) == 7168 * 20480 == 146800640
    assert round(ck.param_count(CFG) / 1e6, 1) == 4849.5
    assert ck.latent_row_bytes(CFG) == 1152


def test_small_case_by_hand():
    # attention: 4x3 + 3x3 + 4x3 + 2x4 + 2x4 = 49; expert 3x4x3 = 36;
    # dense 3x4x5 = 60; always = 2 x 49 + 60 + 1 x 36 = 194
    assert ck.mla_params(SMALL) == 49
    assert ck.expert_params(SMALL) == 36 and ck.dense_ffn_params(SMALL) == 60
    assert ck.always_multiplied_params(SMALL) == 194
    assert ck.router_params(SMALL) == 24 and ck.head_params(SMALL) == 40
    assert ck.param_count(SMALL) == 194 + 24 + 2 * 36 + 80
    assert ck.latent_row_bytes(SMALL) == 6
    # 3 touched experts, 5 pairs: 3 x 36 x 2 B + 5 x 2 x 4 x 2 B; 2 x 5 x 36
    assert ck.moe_experts_cost(SMALL, 3, 5) == {"bytes": 296.0,
                                                "flops": 360.0}
    # 2 sequences, 10 live tokens, 2 layers: rows 10 x 6 B and kv_b 2x1x4
    # x 2 B a layer; per row 1 head x (2 + 2 + 1) MACs, kv_b's 8 a sequence
    assert ck.mla_attend_cost(SMALL, 2, 10) == {
        "bytes": 2 * (60 + 16.0), "flops": 2.0 * 2 * (10 * 5 + 2 * 8)}
    step = ck.decode_step_cost(SMALL, 2, 10, 3, 5)
    assert step["bytes"] == (194 + 40) * 2 + 24 * 4 + 296 + 2 * 10 * 6
    assert step["flops"] == 2 * (194 + 40 + 24) * 2 + 360 + 2 * 2 * 10 * 5
    # a prompt of 3 tokens, 2 pairs held: 2 x (194 + 24) x 3 + 2 x 2 x 36
    # + 2 x 40 + 2 layers x 1 head x 5 x 6 causal pairs x 2
    assert ck.prefill_flops(SMALL, 3, 2) == 1308 + 144 + 80 + 120
    # the mean of prompts of 2 and 4 tokens is not a prompt of 3: (3 + 10)
    # / 2 pairs = 6.5 where 3 tokens have 6
    assert ck.prefill_flops(SMALL, 3, 2, (4 + 16) / 2) \
        == ck.prefill_flops(SMALL, 3, 2) + 2 * 2 * 5 * 0.5


def test_param_count_is_the_programs_tree():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kimi_k2_glue
    from ray_tpu.models.kimi_k2 import KimiK2Model
    cfg = kimi_k2_glue.model_config({
        "factory": "ray_tpu.models.kimi_k2:KimiK2Config", "kwargs": CFG})
    shapes = jax.eval_shape(KimiK2Model(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    matrices = sum(x.size for x in jax.tree_util.tree_leaves(shapes)
                   if x.ndim >= 2)
    assert ck.param_count(CFG) == matrices


def test_a_decode_step_of_the_cell_is_bound_by_memory():
    """32 rows of 6,400 live tokens, 40 touched experts, 21 pairs: the
    issue's reckoning (5.5-6.5 GB of weights, 1.8 GB of latents, least
    ~10 ms)."""
    peaks = cells.peaks_for("TPU v5 lite")
    need = ck.decode_step_cost(CFG, 32, 32 * 6400, 40, 21)
    assert 7.5e9 < need["bytes"] < 9e9
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         peaks)
    assert least["bound"] == "memory" and 0.009 < least["seconds"] < 0.011
    attend = ck.mla_attend_cost(CFG, 32, 32 * 6400)
    assert attend["bytes"] == pytest.approx(7 * (204800 * 1152
                                                 + 512 * 64 * 256 * 2))
